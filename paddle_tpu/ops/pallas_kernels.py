"""Pallas TPU kernels for the fused hot ops (SURVEY.md §3: "pallas reserved
for fused softmax-xent, LN, and flash/ring attention").

flash_attention — blockwise online-softmax attention. The [T, T] score
matrix never hits HBM: each q-block holds running (max, denom, acc) in VMEM
while k/v blocks stream past, so peak memory is O(T·D) instead of O(T²) and
the two matmuls per block ride the MXU back to back. Backward is the
standard flash recompute from the saved logsumexp, also as pallas kernels
(a dK/dV kernel over k-blocks + a dQ kernel over q-blocks, both with
causal block skipping), differentiable via custom_vjp. Every dot in the
three kernels takes its operands in the dtype of q, k, v and accumulates in
float32: bf16 inputs (every AMP program) reach the MXU as bf16, float32
inputs run float32 dots (float32 tolerance in the interpreter; on the v5e
Mosaic runs a float32 dot at default precision as one bf16 pass as well:
same time, same error, my chip run, PR 27). The softmax between the dots
is float32 either way.

softmax_xent — fused log-softmax + label pick over the vocab dim: one VMEM
pass computes the loss and the logsumexp residual; the probability matrix is
only formed in the backward (where it is the gradient anyway), as jax.numpy
that XLA fuses into the operand of whoever reads dlogits. The kernel takes
the logits in the dtype they come in and casts a tile to float32 in VMEM.

Every kernel compiles through Mosaic when the program dispatches to a TPU
and runs the same body in interpret mode elsewhere (the unit tests exercise
the same kernel code path on the CPU).

Parity note: the reference has no fused attention (its transformer builds
q@k^T + softmax + @v from separate ops, paddle/fluid/operators/matmul_op.cc
+ softmax_op.cc); these kernels are the TPU-native upgrade path behind the
same layer APIs.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from .pallas_import import kernel_entry, pl, pltpu

from .kernel_config import DEFAULT_TILES, dispatch_platform

__all__ = ["flash_attention", "softmax_xent", "layer_norm",
           "fused_lstm", "fused_lstmp", "masked_softmax", "masked_pool"]

_NEG = -1e30

# The name each pallas_call gives its Mosaic custom call (HLO: `<name>.<n>`): a
# trace and the benchmark find it by this. ptpu_gated_delta_fwd on: other modules'.
KERNEL_NAMES = (
    "ptpu_flash_fwd", "ptpu_flash_bwd_dkdv", "ptpu_flash_bwd_dq",
    "ptpu_softmax_xent_fwd", "ptpu_layer_norm_fwd", "ptpu_lstm_seq",
    "ptpu_lstmp_seq", "ptpu_masked_softmax", "ptpu_masked_pool",
    "ptpu_gated_delta_fwd", "ptpu_gated_delta_bwd", "ptpu_causal_conv1d_fwd",
    "ptpu_causal_conv1d_bwd", "ptpu_embedding_grad", "ptpu_mhc_pre_fwd",
    "ptpu_mhc_pre_bwd", "ptpu_mhc_post_fwd", "ptpu_mhc_post_bwd",
    "ptpu_mhc_expand", "ptpu_mhc_reduce", "ptpu_mhc_coeffs_fwd",
    "ptpu_mhc_coeffs_bwd", "ptpu_expert_gmm_fwd", "ptpu_expert_gmm_drows",
    "ptpu_expert_gmm_dweights", "ptpu_selective_scan_fwd",
    "ptpu_selective_scan_bwd", "ptpu_ssd_fwd", "ptpu_ssd_bwd")


def _interpret_default():
    """Mosaic exactly when the traced computation dispatches to a TPU;
    everywhere else the same kernel body runs in the interpreter."""
    return dispatch_platform() != "tpu"


def _tile(op, knob, given):
    """A wrapper's block argument: what the caller gave (a sweep, a kernel
    test), else the entry of kernel_config.DEFAULT_TILES, which is what
    every lowering rule runs at."""
    return DEFAULT_TILES[op][knob] if given is None else int(given)


def _vmem_spec(*args, **kwargs):
    kwargs.setdefault("memory_space", pltpu.VMEM)
    return pl.BlockSpec(*args, **kwargs)


_SMEM_WHOLE = pl.BlockSpec(memory_space=pltpu.SMEM)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

# The three kernels below share one rule for precision: a dot takes its
# operands in the dtype the kernel was given and accumulates in float32.
# q, k, v and dO tiles go to the MXU as loaded; s, the running max and sum,
# exp, lse, delta and the acc / dq / dk / dv accumulators are float32 always;
# p and ds, float32 results of VPU work, are cast to the inputs' dtype where
# they enter a dot. The softmax scale multiplies s in float32 after the dot,
# and the dq / dk accumulators once at the end (scaling q or ds in bf16
# would round them once more).

_NT = (((1,), (1,)), ((), ()))       # a @ b.T, as the MXU takes it


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _k_blocks(qb, kv_len, causal, window, block_q, block_k, t_pad):
    """[first, end) of the k blocks a q block streams: none past its causal
    frontier (skipping them halves the attention FLOPs), none entirely past
    this row's key length and, under a window, none entirely older than the
    block's first query can see."""
    nk = jnp.minimum(t_pad // block_k, (kv_len + block_k - 1) // block_k)
    if causal:
        nk = jnp.minimum(nk, ((qb + 1) * block_q + block_k - 1) // block_k)
    if window is None:
        return 0, nk
    return jnp.maximum(qb * block_q - (window - 1), 0) // block_k, nk


def _visible(valid, qpos, kpos, causal, window):
    """The mask of one block step, from `valid` = key j < kv_len: key j is
    visible to query i iff it is valid, and j <= i where causal, and i - j <
    window where there is a window (a query sees the `window` newest keys
    up to itself; without `causal` the keys after it too). qpos and kpos
    broadcast against each other."""
    if causal:
        valid = valid & (qpos >= kpos)
    if window is not None:
        valid = valid & (qpos - kpos < window)
    return valid


# The block-diffusion mask (BD3-LM, arXiv:2503.09573; SDAR's training
# objective): `bd` = (block_length, L), static. The 2 L rows are two copies of
# one sequence of L tokens, the NOISED copy in rows 0 .. L - 1 and the CLEAN
# copy in rows L .. 2 L - 1; row r is (copy, position i = r or r - L, block b
# = i // block_length), and row r sees row s iff: both noised and b_s = b_r
# (block-diagonal, both directions inside a block); or r noised, s clean and
# b_s < b_r (offset block-causal); or both clean and b_s <= b_r
# (block-causal). A clean row never sees a noised one. It is causal in no row
# order, and the rows a tile of rows meets are TWO ranges of tiles.

# added to a noised row's block number: above any block number, so that one
# equality and one inequality on [bq, bk] decide a tile (`_bd_visible`)
_BD_NOISED = 1 << 24


def _cdiv(a, b):
    return (a + b - 1) // b


def _bd_blocks(i, block_i, block_j, bd, transposed=False):
    """((first, end), (first, end)): the two ranges of j tiles (of block_j
    rows) in which tile i (of block_i rows) has a visible pair under the
    block-diffusion mask `bd` = (block_length, L); every tile inside them
    has one and no tile outside has. The first range lies among the noised
    rows (a tile that holds row L - 1), the second among the clean ones,
    after the first and never over it. Not transposed: i is a tile of
    QUERIES and the ranges are of key tiles (a noised query's own diagonal
    blocks, then the clean keys up to the tile's frontier). Transposed: i is
    a tile of KEYS and the ranges are of query tiles (the noised queries of
    a noised key's blocks and those past a clean key's block, then the
    clean queries from a clean key's block on). i may be a traced scalar;
    with Python ints the four bounds are jax scalars all the same."""
    bl, n = bd
    r0 = i * block_i
    r1 = jnp.minimum(r0 + block_i, 2 * n)            # the tile's rows
    has_n, has_c = r0 < n, r1 > n                    # noised, clean rows
    p1 = jnp.minimum(r1, n) - 1                      # its last noised position
    # the noised rows of the blocks the tile's noised rows lie in
    lo = jnp.where(has_n, (r0 // bl) * bl, n)
    hi = jnp.where(has_n, jnp.minimum((p1 // bl + 1) * bl, n), 0)
    if transposed:
        c0 = jnp.maximum(r0, n) - n                  # its first clean position
        # the noised queries past a clean key's block, the clean from it on
        past = jnp.where(has_c, jnp.minimum((c0 // bl + 1) * bl, n), n)
        lo = jnp.minimum(lo, past)
        hi = jnp.where(past < n, n, hi)
        c_lo = n + (c0 // bl) * bl
        c_hi = jnp.where(has_c, 2 * n, c_lo)
    else:
        c1 = r1 - 1 - n                              # its last clean position
        # the clean keys before a noised query's block, up to a clean one's
        reach = jnp.maximum(
            jnp.where(has_n, (p1 // bl) * bl, 0),
            jnp.where(has_c, jnp.minimum((c1 // bl + 1) * bl, n), 0))
        c_lo, c_hi = n, n + reach
    a_first = lo // block_j
    a_end = jnp.where(hi > lo, _cdiv(hi, block_j), a_first)
    b_first = jnp.maximum(c_lo // block_j, a_end)
    b_end = jnp.where(c_hi > c_lo,
                      jnp.maximum(_cdiv(c_hi, block_j), b_first), b_first)
    return (a_first, a_end), (b_first, b_end)


def _bd_block_of(pos, bd):
    """(noised, block number) of rows pos: an int32 vector."""
    bl, n = bd
    noised = pos < n
    i = pos - jnp.where(noised, 0, n)
    return noised, lax.div(i, lax.full_like(i, bl))


def _bd_query_codes(qpos, bd):
    """(equal, at_most) of query rows qpos: a key's code (`_bd_key_code`)
    is visible iff it equals the first or is at most the second."""
    noised, b = _bd_block_of(qpos, bd)
    return jnp.where(noised, b + _BD_NOISED, -1), jnp.where(noised, b - 1, b)


def _bd_key_code(kpos, bd):
    noised, b = _bd_block_of(kpos, bd)
    return jnp.where(noised, b + _BD_NOISED, b)


def _bd_visible(valid, qcodes, kcode):
    """The block-diffusion mask of one block step from the rows' codes, two
    compares on the tile: a noised key (code >= _BD_NOISED) meets the noised
    queries of its own block by the equality and no inequality; a clean key
    (its block number) meets no equality and the inequality of the noised
    queries past its block and the clean ones from it on."""
    equal, at_most = qcodes
    return valid & ((kcode == equal) | (kcode <= at_most))


def _loops(ranges, body, init):
    """`body` over each [first, end) of `ranges` in turn, one carry."""
    for first, end in ranges:
        init = lax.fori_loop(first, end, body, init)
    return init


def heads_a_block(hq, hkv, d):
    """How many query heads one lane block of q as [B, T, Hq*D] holds, from
    the shapes alone; None where a head cannot be indexed in place and the
    arrays go through `_to_bh` first.

    A head is a run of D lanes of a row. Where D is a multiple of 128 it is
    whole lane blocks by itself (1), and a grouped key/value head is found
    by the block's index. Where D divides 128 and the heads fill whole
    blocks, 128 // D heads share one (2 at D=64); where all the heads
    together are under 128 lanes the block is the whole row. The last two
    need as many key/value heads as query heads: a block of K has to hold
    the same heads at the same lanes as the block of Q it meets. A head of
    192 (latent attention's 128 + 64) is none of these (32, 32, 192 gives
    None): it does not come here whole but as its two parts, q, k and v at
    128 (1 a block) and the rotary part beside them (`_rope_rows`: two
    heads of 64 a block, against one key). Nor is a part without position
    of 192 beside a value of 256 (20, 20, 192: None, a head and a half a
    lane block): that head comes here whole, 192 + 64 = 256 on 256 (1 a
    block), joined by `flash_attention` (`latent_form`)."""
    if d % 128 == 0:
        return 1
    if hq == hkv and 128 % d == 0 and (hq * d) % 128 == 0:
        return 128 // d
    if hq == hkv and hq * d < 128:
        return hq
    return None


# What these helpers and the index maps add to a kernel is written with lax
# primitives, not jnp functions or operators on tracers: each of those is a
# call of a jitted function, half a millisecond of a step's trace on the
# chip's host (2,000 such calls more were 2 s of `jaxpr_trace_s` at T=2048,
# 9 % of `setup_s`, while a kernel's body and index maps were traced anew at
# every call site; my chip run, PR 38. Since PR 60 they are traced once a
# shape: ops/pallas_import.py `kernel_entry`).

def _head_lanes(w, d, hb):
    """[1, W] mask of the D lanes of the head this grid step works on, of
    the hb heads its W-lane blocks hold; None for one head a block."""
    if hb == 1:
        return None
    lane = lax.broadcasted_iota(jnp.int32, (1, w), 1)
    return lax.eq(lax.div(lane, lax.full_like(lane, d)),
                  lax.broadcast(pl.program_id(3), (1, w)))


def _select(lanes, x, other):
    return lax.select(lax.broadcast_in_dim(lanes, x.shape, (0, 1)), x, other)


def _only(lanes, x):
    """x with every lane outside the head's set to zero. A dot that
    contracts the W lanes of such a block with a whole block then adds
    exact zeros for the other heads: the head's own D-deep product, bit for
    bit, at the depth D < 128 pads to on the MXU anyway."""
    return x if lanes is None else _select(lanes, x, lax.full_like(x, 0))


def _put(lanes, ref, x):
    """Write a [rows, W] result to the block `ref` holds: all of it, or the
    head's lanes of it. The block keeps its index across the heads-in-block
    grid axis, so it stays in VMEM until every head has put its lanes and
    is written back once."""
    ref[...] = x if lanes is None else _select(lanes, x, ref[...])


def _as_row(col):
    """[n, 1] -> [1, n] inside a kernel: the statistics of a q block are
    columns where the arithmetic uses them ([bq, bk] tiles, keys in the
    lanes) and rows in HBM (lane-dense). One XLU transpose of a 128-lane
    broadcast, once a q block."""
    n = col.shape[0]
    wide = lax.broadcast_in_dim(col, (n, 128), (0, 1))
    return lax.slice(lax.transpose(wide, (1, 0)), (0, 0), (1, n))


def _as_col(row):
    """[1, n] -> [n, 1], the inverse of `_as_row`."""
    n = row.shape[1]
    tall = lax.broadcast_in_dim(row, (128, n), (0, 1))
    return lax.slice(lax.transpose(tall, (1, 0)), (0, 0), (n, 1))


def _rope_lanes(w, dr, hr):
    """[1, W] mask of the dr lanes that hold this grid step's head among
    the hr heads of a block of the rotary queries [.., Hq*dr] (the head is
    grid axis 1: the latent form runs one head a step); None where a head
    is whole blocks."""
    if hr == 1:
        return None
    lane = lax.broadcasted_iota(jnp.int32, (1, w), 1)
    member = lax.rem(pl.program_id(1), np.int32(hr))
    return lax.eq(lax.div(lane, lax.full_like(lane, dr)),
                  lax.broadcast(member, (1, w)))


def _lin(*terms):
    """sum(index * n) over (index, n) terms of an index map."""
    total = None
    for x, n in terms:
        x = x if n == 1 else lax.mul(x, np.int32(n))
        total = x if total is None else lax.add(total, x)
    return total


# The running max of a row starts at a finite floor above `_NEG`: a masked
# score is `_NEG`, so exp(_NEG - floor) is exactly 0 and a row that has met
# no visible key yet gets p = 0 from the `exp` itself, with no second select
# on the tile; real scores are far above the floor, so it never wins a max.
_M_FLOOR = _NEG / 2


def _partial_lanes(n):
    """Lanes of a row sum's partials over blocks of n keys: one lane tile,
    or n itself where it is no whole lane tiles (the tests' small blocks)."""
    return n if n % 128 else 128


def _lane_sums(p):
    """[rows, n] -> [rows, `_partial_lanes(n)`]: the lane tiles of p added
    elementwise (VALU adds of whole vregs, nothing crosses a lane)."""
    rows, n = p.shape
    lanes = _partial_lanes(n)
    total = lax.slice(p, (0, 0), (rows, lanes))
    for i in range(lanes, n, lanes):
        total = lax.add(total, lax.slice(p, (0, i), (rows, i + lanes)))
    return total


def _rescaled(ref, corr, term):
    """ref * corr + term, corr a [rows, 1] column."""
    return lax.add(lax.mul(ref[...], lax.broadcast_in_dim(
        corr, ref.shape, (0, 1))), term)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, *refs, scale, causal, window,
                      block_q, block_k, t_pad, d, hb, rope=None, bd=None):
    """One q block's output and logsumexp: the k blocks `_k_blocks` names
    stream past it. Of the online softmax's state only the running max is
    carried by the loop; the row sums `l_ref` [bq, 128] and the output's
    accumulator `acc_ref` [bq, W] are VMEM scratch, read and written once a
    block. Carried, the three are 192 vregs across the loop's back edge on
    64 registers, and the compiler moved them from spill slot to spill slot
    at the loop's head and tail: 275 of a block step's 1,730 bundles in which
    the MXU had nothing to run, beside the spills inside (AOT compile, PR
    46). A row's sum is kept as 128 lane partials and reduced across lanes
    once, after the loop, so that the loop's one cross-lane reduction is the
    max.

    `rope` (dr, hr), the latent form: two operands more, the rotary
    queries' block [bq, Wr] and the one rotary key all heads share, pinned
    [t_pad, Wr]; a score is the sum of the two products.

    `bd` (block_length, L), the block-diffusion mask: the k blocks are
    `_bd_blocks`'s two ranges, one loop after the other over one carry, and
    a tile is masked by `_bd_visible`."""
    if rope:
        qr_ref, kr_ref, len_ref, o_ref, lse_ref, l_ref, acc_ref = refs
        qr = _only(_rope_lanes(qr_ref.shape[1], *rope), qr_ref[...])
    else:
        len_ref, o_ref, lse_ref, l_ref, acc_ref = refs
    qb = pl.program_id(2)
    bq, w = q_ref.shape
    lanes = _head_lanes(w, d, hb)
    q = _only(lanes, q_ref[...])                             # [bq, W]
    qpos = qb * block_q + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    # whole [B, 1] array lives in SMEM (a (1,1)-blocked spec violates
    # Mosaic's (8,128) block rule — caught on first real-TPU run, round 4)
    kv_len = len_ref[pl.program_id(0), 0]                    # this row's T
    l_ref[...] = lax.full(l_ref.shape, 0.0, jnp.float32)
    acc_ref[...] = lax.full(acc_ref.shape, 0.0, jnp.float32)
    qcodes = _bd_query_codes(qpos, bd) if bd else None

    def body(kb, m):
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        if rope:
            s = (_dot(q, k, _NT) + _dot(
                qr, kr_ref[pl.ds(kb * block_k, block_k), :], _NT)) * scale
        else:
            s = _dot(q, k, _NT) * scale                      # [bq, bk] f32
        kpos = kb * block_k + lax.broadcasted_iota(jnp.int32, (1, block_k),
                                                   1)
        valid = _bd_visible(kpos < kv_len, qcodes, _bd_key_code(kpos, bd)) \
            if bd else _visible(kpos < kv_len, qpos, kpos, causal, window)
        s = jnp.where(valid, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                               # masked -> 0
        corr = jnp.exp(m - m_new)
        l_ref[...] = _rescaled(l_ref, corr, _lane_sums(p))
        acc_ref[...] = _rescaled(acc_ref, corr, _dot(p.astype(v.dtype), v))
        return m_new

    m = _loops(
        _bd_blocks(qb, block_q, block_k, bd) if bd else (_k_blocks(
            qb, kv_len, causal, window, block_q, block_k, t_pad),), body,
        lax.full((bq, 1), _M_FLOOR, jnp.float32))

    l = lax.broadcast_in_dim(lax.reduce_sum(l_ref[...], (1,)), (bq, 1), (0,))
    # a row that saw no key at all (kv_len 0, padding past a window): out 0
    # and the lse a max still at `_NEG` gives
    m = lax.select(lax.gt(l, lax.full_like(l, 0.0)), m,
                   lax.full_like(m, _NEG))
    l_safe = jnp.maximum(l, 1e-30)
    _put(lanes, o_ref, (acc_ref[...] / l_safe).astype(o_ref.dtype))
    lse_ref[0, 0] = _as_row(m + jnp.log(l_safe))             # [1, bq]


def _kv_row(n_q, n_kv):
    """The index map's coordinate of the K/V row (or head) a query row (or
    head) reads: n_q query rows on n_kv of K and V, the group's members
    next to each other, so query b reads b // group. The grouped K/V are
    found here, by the index map, and never repeated in HBM; with as many
    of one as of the other the map is the identity."""
    group = n_q // n_kv
    return (lambda b: b) if group == 1 \
        else (lambda b: lax.div(b, np.int32(group)))


def _pad_t(t, block_q, block_k):
    """T padded so that BOTH the q grid and the k loop divide exactly
    (mismatched block sizes otherwise drop tail k blocks / leave q rows
    unwritten)."""
    blk = int(np.lcm(block_q, block_k))
    return int(-(-t // blk) * blk)


def _rope_rows(rope, d, hb, pad):
    """The latent form's two operands as the kernels take them, and the
    static (dr, hr) the kernels are built with: the rotary queries [rows, T,
    Hq*dr], hr = 128 // dr heads a lane block, and the one rotary key
    [rows, T, dr] repeated to a block's width: a head's lanes of a block
    of queries meet the key at the same lanes (`_rope_lanes`), and the MXU
    pads a contraction of 64 to 128 deep anyway. The repeat is T x 128,
    not a key a head."""
    qr, kr = rope
    dr = kr.shape[2]
    if hb != 1 or d % 128 or not (128 % dr == 0 or dr % 128 == 0) \
            or qr.shape[2] % max(dr, 128):
        raise ValueError(
            "flash_attention: the latent form takes heads whose q, k and v "
            "are whole lane blocks (a multiple of 128 wide) and a rotary part "
            "that divides 128 or is a multiple of it, its heads filling "
            "whole blocks; got %d and %d" % (d, dr))
    hr = max(1, 128 // dr)
    if hr > 1:
        kr = jnp.tile(kr, (1, 1, hr))
    if pad:
        qr, kr = (jnp.pad(a, pad) for a in (qr, kr))
    return (qr.reshape(-1, qr.shape[2]), kr.reshape(-1, kr.shape[2])), \
        (dr, hr)


# What the three flash entries are built under, beside their arrays: static
# arguments of their jits. `rows`: the query rows (the arrays come as [rows *
# t_pad, lanes]); `rope`: None, or the latent form's (dr, hr) of `_rope_rows`.
_FLASH_STATIC = ("rows", "d", "hb", "scale", "causal", "window", "block_q",
                 "block_k", "rope", "interpret", "bd")


def _flash_static(q, k, rows, d, hb):
    """(t_pad, heads, W, the K/V row of a query row, the K/V head of a
    query head) of a flash entry's [rows * t_pad, H*D] operands."""
    t_pad = q.shape[0] // rows
    return t_pad, q.shape[1] // d, hb * d, \
        _kv_row(rows, k.shape[0] // t_pad), \
        _kv_row(q.shape[1] // d, k.shape[1] // d)


@kernel_entry("ptpu_flash_fwd", static_argnames=_FLASH_STATIC)
def _flash_fwd_call(q, k, v, rope_args, lens, *, rows, d, hb, scale, causal,
                    window, block_q, block_k, rope, interpret, bd=None):
    """The forward pallas_call on `_flash_fwd`'s operands as it lays them
    out: q [rows * t_pad, Hq*D], k, v [rows_kv * t_pad, Hkv*D], rope_args
    () or `_rope_rows`'s two, lens [rows, 1] int32 -> (out as q, lse)."""
    hd = q.shape[1]
    t_pad, heads, w, kv_b, kv_h = _flash_static(q, k, rows, d, hb)
    nq = t_pad // block_q

    def q_block(b, p, i, hh):
        return _lin((b, nq), (i, 1)), p

    def kv_pair(b, p, i, hh):        # the whole K or V of the head's row
        return kv_b(b), kv_h(p)

    static = dict(scale=scale, causal=causal, window=window,
                  block_q=block_q, block_k=block_k, t_pad=t_pad, d=d, hb=hb,
                  bd=bd)
    rope_specs = []
    if rope is not None:
        static["rope"] = rope
        hr = rope[1]
        wr = rope_args[1].shape[1]
        rope_specs = [
            _vmem_spec((block_q, wr), lambda b, p, i, hh: (
                _lin((b, nq), (i, 1)), lax.div(p, np.int32(hr)))),
            _vmem_spec((t_pad, wr), lambda b, p, i, hh: (kv_b(b), 0))]
    kernel = functools.partial(_flash_fwd_kernel, **static)
    # lens: whole array in SMEM (no blocking); lse: a row of block_q lanes
    # a (head, q block): Mosaic requires the last two block dims divisible
    # by (8, 128) or equal to the array's, and (1, block_q) is the array's
    return pl.pallas_call(
        kernel,
        grid=(rows, heads // hb, nq, hb),
        in_specs=[
            _vmem_spec((block_q, w), q_block),
            _vmem_spec((t_pad, w), kv_pair),
            _vmem_spec((t_pad, w), kv_pair),
        ] + rope_specs + [_SMEM_WHOLE],
        out_specs=[
            _vmem_spec((block_q, w), q_block),
            _vmem_spec((1, 1, 1, block_q), lambda b, p, i, hh: (
                _lin((b, heads), (p, hb), (hh, 1)), i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows * t_pad, hd), q.dtype),
            jax.ShapeDtypeStruct((rows * heads, nq, 1, block_q),
                                 jnp.float32),
        ],
        # the row sums' lane partials and the output's accumulator
        scratch_shapes=[
            pltpu.VMEM((block_q, _partial_lanes(block_k)), jnp.float32),
            pltpu.VMEM((block_q, w), jnp.float32)],
        interpret=interpret,
        name="ptpu_flash_fwd",
    )(q, k, v, *rope_args, lens)


def _flash_fwd(q, k, v, kv_len, d, hb, scale, causal, window, block_q,
               block_k, interpret, rope=None, bd=None):
    """q: [Bq, T, Hq*D]; k, v: [Bk, T, Hkv*D], heads of D lanes side by
    side in a row; kv_len: [Bq] int32 (true key length per query row); hb
    query heads a lane block (`heads_a_block`) -> (out [Bq, T, Hq*D], lse
    [Bq*Hq, nq, 1, block_q] over the padded T). The grid is (row, block of
    heads, q block, head in its block); a head's blocks are W = hb * D
    lanes wide at lane block `head // hb`, and K/V rows and heads may be
    fewer than the queries' (`_kv_row`, both). The pallas_call takes the
    arrays as [Bq * T, Hq*D], tokens by features, the shape the projections
    around the op give and take: with [B, T, H*D] operands XLA laid the
    neighbouring matmuls' operands out tokens-minor, and in the OLMoE cell,
    at its memory limit, scheduled and rematerialised its way to a step 15
    ms slower (my chip run and AOT compile, PR 38). Beside the blocks a grid
    step has two float32 scratch arrays, the kernel's own state across its k
    loop: the row sums as lane partials [block_q, 128] and the output's
    accumulator [block_q, W], 0.25 MiB each at 512 rows and W=128. The
    call itself is `_flash_fwd_call`, traced once a shape; the pad, the
    reshapes and the latent form's repeat stay here, under the op's scope."""
    rows, t, hd = q.shape
    t_pad = _pad_t(t, block_q, block_k)
    pad = [(0, 0), (0, t_pad - t), (0, 0)] if t_pad != t else None
    if pad:
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
    q, k, v = (a.reshape(-1, a.shape[2]) for a in (q, k, v))
    rope_args, rope_static = (), None
    if rope is not None:
        rope_args, rope_static = _rope_rows(rope, d, hb, pad)
    out, lse = _flash_fwd_call(
        q, k, v, rope_args, kv_len.reshape(rows, 1).astype(jnp.int32),
        rows=rows, d=d, hb=hb, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, rope=rope_static,
        interpret=interpret, bd=bd)
    out = out.reshape(rows, t_pad, hd)
    return (out if t_pad == t else out[:, :t]), lse


def _flash_bwd_dkdv_kernel(q_ref, g_ref, k_ref, v_ref, lse_ref, delta_ref,
                           *refs, scale, causal, window, block_q, block_k,
                           t_pad, d, hb, rope=None, bd=None):
    """One k-block's dK/dV: stream q-blocks past it, starting at the
    causal frontier (q blocks strictly before this k block contribute
    nothing — the same 2x FLOP skip the forward kernel does) and, under a
    window, ending at the last q block that can still see the k block.

    The block step works on the transposed scores, s.T = k @ q.T [bk, bq]:
    p.T and ds.T then enter their dots as they are, with no transpose of a
    [bq, bk] tile a block (a fifth to a quarter of this kernel's time, my
    chip run, PR 27), and lse / delta are rows [1, bq] that broadcast down
    the sublanes, as they lie in HBM: [nq, 1, block_q] a head.

    `rope` (dr, hr), the latent form: the head's rotary queries pinned
    beside q and dO, the k block's rows of the shared rotary key, and a
    third result, this head's float32 share of that key's gradient (the
    heads' shares are summed after the kernel, as a group's are).

    `bd`, the block-diffusion mask: the q blocks are `_bd_blocks`'s two
    ranges transposed, the forward loop's mirror."""
    if rope:
        qr_ref, kr_ref, len_ref, dk_ref, dv_ref, dkr_ref = refs
        rlanes = _rope_lanes(qr_ref.shape[1], *rope)
        kr = kr_ref[...]
    else:
        len_ref, dk_ref, dv_ref = refs
    kb = pl.program_id(2)
    bk, w = k_ref.shape
    lanes = _head_lanes(w, d, hb)
    k = _only(lanes, k_ref[...])                             # [bk, W]
    v = _only(lanes, v_ref[...])
    kpos = kb * block_k + lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    kv_len = len_ref[pl.program_id(0), 0]
    nq = t_pad // block_q
    qb0 = (kb * block_k) // block_q if causal else 0
    # key-padding early exit (mirror of the forward's): a k block entirely
    # past this row's length contributes nothing — skip its q loop
    qb0 = jnp.where(kb * block_k >= kv_len, nq, qb0)
    if window is not None:
        # the block's newest key, (kb + 1) * block_k - 1, is seen last by
        # the query window - 1 after it
        nq = jnp.minimum(nq, ((kb + 1) * block_k + window - 2) // block_q + 1)
    ranges = ((qb0, nq),)
    if bd:
        ranges = _bd_blocks(kb, block_k, block_q, bd, transposed=True)
        kcode = _bd_key_code(kpos, bd)

    def step(qb):
        """This q block's terms of (dk, dv)."""
        q = q_ref[pl.ds(qb * block_q, block_q), :]           # [bq, W]
        g = g_ref[pl.ds(qb * block_q, block_q), :]
        lse = lse_ref[0, qb]                                 # [1, bq] f32
        delta = delta_ref[0, qb]
        valid = kpos < kv_len
        if causal or window is not None or bd:
            qpos = qb * block_q + lax.broadcasted_iota(
                jnp.int32, (1, block_q), 1)
            valid = _bd_visible(valid, _bd_query_codes(qpos, bd), kcode) \
                if bd else _visible(valid, qpos, kpos, causal, window)
        if rope:
            qr = _only(rlanes, qr_ref[pl.ds(qb * block_q, block_q), :])
            st = (_dot(k, q, _NT) + _dot(kr, qr, _NT)) * scale
        else:
            st = _dot(k, q, _NT) * scale
        p = jnp.where(valid, jnp.exp(st - lse), 0.0)
        ds = p * (_dot(v, g, _NT) - delta)
        terms = (_dot(ds.astype(q.dtype), q),
                 _dot(p.astype(g.dtype), g))                 # p [bk, bq]
        return terms + (_dot(ds.astype(q.dtype), qr),) if rope else terms

    zeros = jnp.zeros((bk, w), jnp.float32)
    if rope:
        def body(qb, carry):
            return tuple(a + b for a, b in zip(carry, step(qb)))
        dk, dv, dkr = _loops(ranges, body, (
            zeros, zeros, jnp.zeros(kr.shape, jnp.float32)))
        dk_ref[...] = (dk * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv.astype(dv_ref.dtype)
        dkr_ref[...] = dkr * scale
    elif dk_ref.dtype == jnp.float32 and lanes is None:
        # a group's float32 shares: the output blocks are the accumulators,
        # and no [bk, W] float32 pair is carried beside them (2.3 MiB of
        # VMEM at W=256: what T=4096 at D=256 does not have to spare)
        dk_ref[...] = dv_ref[...] = zeros

        def body(qb, carry):
            dk, dv = step(qb)
            dk_ref[...] += dk
            dv_ref[...] += dv
            return carry
        _loops(ranges, body, 0)
        dk_ref[...] *= scale
    else:
        def body(qb, carry):
            dk, dv = step(qb)
            return carry[0] + dk, carry[1] + dv
        dk, dv = _loops(ranges, body, (zeros, zeros))
        _put(lanes, dk_ref, (dk * scale).astype(dk_ref.dtype))
        _put(lanes, dv_ref, dv.astype(dv_ref.dtype))


def _flash_bwd_dq_kernel(q_ref, g_ref, o_ref, k_ref, v_ref, lse_ref,
                         *refs, scale, causal, window, block_q, block_k,
                         t_pad, d, hb, rope=None, bd=None):
    """One q-block's dQ: stream the k-blocks between the window's edge and
    the causal / key-length frontier (mirror of the forward loop). Before
    the loop, delta = rowsum(dO * O) of the block's own rows, from the dO
    block it holds anyway and the O block beside it: the column its tiles
    want, and written out as a row for the dK/dV kernel, which runs after
    this one.

    `rope` (dr, hr), the latent form: the rotary queries' block and the
    shared rotary key, pinned, and a third result, the rotary queries'
    gradient, the head's lanes of a block whose other lanes are zero: the
    hr heads of a block write hr blocks (their sum is taken after the
    kernel), since the grid meets them q blocks apart.

    `bd`, the block-diffusion mask: the forward kernel's two ranges and
    its tile mask."""
    if rope:
        qr_ref, kr_ref, len_ref, dq_ref, delta_ref, dqr_ref = refs
        rlanes = _rope_lanes(qr_ref.shape[1], *rope)
        qr = _only(rlanes, qr_ref[...])
    else:
        len_ref, dq_ref, delta_ref = refs
    qb = pl.program_id(2)
    bq, w = q_ref.shape
    lanes = _head_lanes(w, d, hb)
    q = _only(lanes, q_ref[...])                             # [bq, W]
    g = _only(lanes, g_ref[...])
    lse = _as_col(lse_ref[0, 0])                             # [bq, 1] f32
    delta = jnp.sum(g.astype(jnp.float32) * o_ref[...].astype(jnp.float32),
                    axis=-1, keepdims=True)
    delta_ref[0, 0] = _as_row(delta)
    qpos = qb * block_q + lax.broadcasted_iota(jnp.int32, (bq, 1), 0)
    kv_len = len_ref[pl.program_id(0), 0]
    qcodes = _bd_query_codes(qpos, bd) if bd else None

    def body(kb, dq):
        k = k_ref[pl.ds(kb * block_k, block_k), :]
        v = v_ref[pl.ds(kb * block_k, block_k), :]
        kpos = kb * block_k + lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        valid = _bd_visible(kpos < kv_len, qcodes, _bd_key_code(kpos, bd)) \
            if bd else _visible(kpos < kv_len, qpos, kpos, causal, window)
        if rope:
            kr = kr_ref[pl.ds(kb * block_k, block_k), :]
            s = (_dot(q, k, _NT) + _dot(qr, kr, _NT)) * scale
        else:
            s = _dot(q, k, _NT) * scale
        p = jnp.where(valid, jnp.exp(s - lse), 0.0)
        ds = p * (_dot(g, v, _NT) - delta)
        if rope:
            return (dq[0] + _dot(ds.astype(k.dtype), k),
                    dq[1] + _dot(ds.astype(k.dtype), kr))
        return dq + _dot(ds.astype(k.dtype), k)

    zeros = jnp.zeros((bq, w), jnp.float32)
    dq = _loops(
        _bd_blocks(qb, block_q, block_k, bd) if bd else (_k_blocks(
            qb, kv_len, causal, window, block_q, block_k, t_pad),), body,
        (zeros, jnp.zeros(qr.shape, jnp.float32)) if rope else zeros)
    if rope:
        dq, dqr = dq
        dqr_ref[...] = _only(rlanes, (dqr * scale).astype(dqr_ref.dtype))
    _put(lanes, dq_ref, (dq * scale).astype(dq_ref.dtype))


def _rope_member(hr):
    """A head `p` of the latent form as (its place among the hr heads of
    its block of rotary lanes, that block)."""
    return lambda p: (lax.rem(p, np.int32(hr)), lax.div(p, np.int32(hr)))


@kernel_entry("ptpu_flash_bwd_dq", static_argnames=_FLASH_STATIC)
def _flash_bwd_dq_call(q, g, out, k, v, lse, rope_args, lens, *, rows, d, hb,
                       scale, causal, window, block_q, block_k, rope,
                       interpret, bd=None):
    """The dQ pallas_call on `_flash_bwd`'s operands as it lays them out
    (`_flash_fwd_call`'s, dO and O as q) -> (dq as q, delta as lse, and
    under the latent form the rotary queries' gradient in hr slabs)."""
    hd = q.shape[1]
    t_pad, heads, w, kv_b, kv_h = _flash_static(q, k, rows, d, hb)
    nq = t_pad // block_q
    static = dict(scale=scale, causal=causal, window=window,
                  block_q=block_q, block_k=block_k, t_pad=t_pad, d=d, hb=hb,
                  bd=bd)
    rope_specs, rope_out, rope_shape = [], [], []
    if rope is not None:
        static["rope"] = rope
        hr = rope[1]
        member = _rope_member(hr)
        wq, wr = (a.shape[1] for a in rope_args)
        rope_specs = [
            _vmem_spec((block_q, wr), lambda b, p, i, hh: (
                _lin((b, nq), (i, 1)), member(p)[1])),
            _vmem_spec((t_pad, wr), lambda b, p, i, hh: (kv_b(b), 0))]
        rope_out = [_vmem_spec((block_q, wr), lambda b, p, i, hh: (
            _lin((member(p)[0], rows * nq), (b, nq), (i, 1)), member(p)[1]))]
        rope_shape = [jax.ShapeDtypeStruct((hr * rows * t_pad, wq), q.dtype)]

    def stat_block(b, p, i, hh):     # a head's (1, block_q) of q block i
        return _lin((b, heads), (p, hb), (hh, 1)), i, 0, 0

    def q_block(b, p, i, hh):
        return _lin((b, nq), (i, 1)), p

    def kv_pair(b, p, i, hh):        # the whole K or V of the head's row
        return kv_b(b), kv_h(p)

    return pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **static),
        grid=(rows, heads // hb, nq, hb),
        in_specs=[
            _vmem_spec((block_q, w), q_block),                         # q
            _vmem_spec((block_q, w), q_block),                         # g
            _vmem_spec((block_q, w), q_block),                         # o
            _vmem_spec((t_pad, w), kv_pair),                           # k
            _vmem_spec((t_pad, w), kv_pair),                           # v
            _vmem_spec((1, 1, 1, block_q), stat_block),                # lse
        ] + rope_specs + [_SMEM_WHOLE],
        out_specs=[
            _vmem_spec((block_q, w), q_block),
            _vmem_spec((1, 1, 1, block_q), stat_block),              # delta
        ] + rope_out,
        out_shape=[jax.ShapeDtypeStruct((rows * t_pad, hd), q.dtype),
                   jax.ShapeDtypeStruct(lse.shape, jnp.float32)]
        + rope_shape,
        interpret=interpret,
        name="ptpu_flash_bwd_dq",
    )(q, g, out, k, v, lse, *rope_args, lens)


@kernel_entry("ptpu_flash_bwd_dkdv", static_argnames=_FLASH_STATIC)
def _flash_bwd_dkdv_call(q, g, k, v, lse, delta, rope_args, lens, *, rows, d,
                         hb, scale, causal, window, block_q, block_k, rope,
                         interpret, bd=None):
    """The dK/dV pallas_call on `_flash_bwd`'s operands as it lays them
    out -> (dk, dv [rows * group * t_pad, Hkv*D], a query head's share in
    the slab of its place in its group, float32 where there is a group,
    and under the latent form every head's float32 share of the shared
    rotary key's gradient)."""
    t_pad, heads, w, kv_b, kv_h = _flash_static(q, k, rows, d, hb)
    heads_kv = k.shape[1] // d
    nq, nk = t_pad // block_q, t_pad // block_k
    # grouped queries: the kernel runs a query head at a time, as it does
    # ungrouped, and gives that head's float32 share of its K/V head's
    # gradient; the group's shares are summed after it (`_flash_bwd` says
    # why after and not inside)
    grouped = q.shape != k.shape
    static = dict(scale=scale, causal=causal, window=window,
                  block_q=block_q, block_k=block_k, t_pad=t_pad, d=d, hb=hb,
                  bd=bd)
    rope_specs, rope_out, rope_shape = [], [], []
    if rope is not None:
        static["rope"] = rope
        member = _rope_member(rope[1])
        wr = rope_args[1].shape[1]
        rope_specs = [
            _vmem_spec((t_pad, wr), lambda b, p, j, hh: (b, member(p)[1])),
            _vmem_spec((block_k, wr), lambda b, p, j, hh: (
                _lin((kv_b(b), nk), (j, 1)), 0))]
        rope_out = [_vmem_spec((block_k, wr), lambda b, p, j, hh: (
            _lin((b, heads * nk), (p, nk), (j, 1)), 0))]
        rope_shape = [jax.ShapeDtypeStruct((rows * heads * t_pad, wr),
                                           jnp.float32)]

    def stat_row(b, p, j, hh):       # a head's whole [nq, 1, block_q]
        return _lin((b, heads), (p, hb), (hh, 1)), 0, 0, 0

    # a head's dK and dV blocks lie where its K and V blocks do, in the slab
    # of its place in its group: [rows * group * T, Hkv*D], which is [rows *
    # T, Hq*D] where there is no group, and a sum over slabs where there is
    group = heads // heads_kv

    def k_block(b, p, j, hh):
        return _lin((kv_b(b), nk), (j, 1)), kv_h(p)

    def share(b, p, j, hh):
        if group == 1:
            return _lin((b, nk), (j, 1)), p
        member = lax.rem(p, np.int32(group))
        return _lin((b, group * nk), (member, nk), (j, 1)), kv_h(p)

    return pl.pallas_call(
        functools.partial(_flash_bwd_dkdv_kernel, **static),
        grid=(rows, heads // hb, nk, hb),
        in_specs=[
            _vmem_spec((t_pad, w), lambda b, p, j, hh: (b, p)),         # q
            _vmem_spec((t_pad, w), lambda b, p, j, hh: (b, p)),         # g
            _vmem_spec((block_k, w), k_block),                         # k
            _vmem_spec((block_k, w), k_block),                         # v
            _vmem_spec((1, nq, 1, block_q), stat_row),                # lse
            _vmem_spec((1, nq, 1, block_q), stat_row),              # delta
        ] + rope_specs + [_SMEM_WHOLE],
        out_specs=[_vmem_spec((block_k, w), share)] * 2 + rope_out,
        out_shape=[jax.ShapeDtypeStruct(
            (rows * group * t_pad, heads_kv * d),
            jnp.float32 if grouped else k.dtype)] * 2 + rope_shape,
        interpret=interpret,
        name="ptpu_flash_bwd_dkdv",
    )(q, g, k, v, lse, delta, *rope_args, lens)


def _flash_bwd(d, hb, scale, causal, window, block_q, block_k, interpret,
               res, g, rope=None, bd=None):
    """Flash backward as two pallas kernels (standard flash-attention recompute
    from the saved logsumexp — the [T, T] matrix never exists): a dK/dV
    kernel gridded over k-blocks and a dQ kernel gridded over q-blocks,
    both with causal block skipping. Their dots run in the dtype of q, k, v
    and dO (bf16 under AMP, float32 for float32 inputs) with float32
    accumulation; p is recomputed in float32 from the float32 lse, and p
    and ds are cast to that dtype where they enter a dot.

    Operands are as `_flash_fwd`'s: q, dO and O [Bq, T, Hq*D], k, v [Bk, T,
    Hkv*D] (tokens by features in the calls), lse [Bq*Hq, nq, 1, block_q];
    a head's blocks are W = hb * D lanes wide. The dQ kernel runs first: it
    makes delta = rowsum(dO * O) of its q block from the dO block it holds
    and the O block beside it, and writes it as a row, shaped as lse, for
    the dK/dV kernel. With two heads a block (D=64) the head in its block
    is the innermost grid axis: the q (or k) block, the pinned pair and the
    output block keep their index across its steps, so each is fetched once
    and the output written back once; a step masks the other head's lanes
    out of the two operands it loads once a block (`_only`) and selects its
    own lanes of the result (`_put`).

    VMEM budget, at the benchmark's shapes and the default 512 x 512 blocks
    (16 MiB scoped a core on the v5e). Each kernel pins one full [t_pad, W]
    operand pair a grid step (q+dO for dK/dV, k+v for dQ), W = 128 at D=64
    and D above: a bf16 operand is t_pad * 256 B at W=128: 0.5 MiB at
    T=2048 (D=64, two heads), 1 MiB at T=4096 (D=128), 2 and 4 MiB for the
    pair double-buffered. The [bq, bk] float32 tiles of a block step (s, p,
    dp, ds, and the bf16 copies of p and ds) are 1 MiB each at 512 x 512,
    about 5 MiB live. lse and delta are rows of block_q lanes everywhere:
    the forward writes lse, and dQ reads lse and writes delta, one (1,
    block_q) block a step, and the column their [bq, bk] tiles want is
    turned to or from that row inside the kernel (`_as_row`, `_as_col`);
    the dK/dV kernel needs a head's whole rows and takes [nq, 1, block_q]
    (16 KiB each at T=4096). As (block_q, 1) columns of [B*H, T, 1] they
    were one lane in 128: 256 KiB a block in VMEM and 64 MiB an array in
    HBM at T=2048. Mosaic takes every pair of {128, 256, 512} x {128, 256,
    512, 1024} at both shapes and 1024 x 512; it refuses 1024 x 1024 at
    D=128 (my chip run, PR 27). The pinned pair sets the longest sequence.
    At T=4096, D=256 with float32 dK and dV blocks (grouped queries) the
    dK/dV kernel needed 16.8 MiB with a [bk, W] float32 pair carried beside
    the blocks, and 14.5 with the blocks as their own accumulators (AOT
    compile, PR 38; the [BH, T, D] form needed the same 16.8 and compiled
    where XLA happened to hold an operand in VMEM already). At T=8192,
    D=128 a bf16 operand is 2 MiB and the pair double-buffered 8 MiB, 13
    MiB with the tiles: all three kernels compile at 512 x 512 and run
    there (7 query heads on 1 key/value head: forward 1.40 ms, forward and
    backward 4.72 ms a layer full, 1.19 and 3.89 ms under a window of 4096;
    my chip run, PR 31). T=16384 compiles at no block size (AOT compile, PR
    27). Streaming the pair through a second grid axis is the follow-up;
    ring/Ulysses SP is the intended path for those lengths
    (parallel/ring_attention.py).

    Grouped queries (q with group x as many heads as k and v): the index
    maps send query head h to K/V head h // group (`_kv_row`), so the
    pinned pair of the forward and dQ kernels is fetched once a group and
    not once a head, and nothing is repeated in HBM. dK/dV of a key/value
    head is the sum over its group, taken AFTER the kernel: it runs a query
    head at a time, as ungrouped, writes that head's share in float32, in
    the slab of the head's place in its group ([group * T, Hkv*D]: summed
    over the leading axis the shares need no relayout; as lanes of one [T,
    Hq*D] array XLA transposed all of them first), and XLA sums the group.
    Summing inside the kernel would put the group on a further, innermost
    grid axis and re-fetch the pinned q and dO pair (4 MiB) at every step
    of it, 16 k blocks x 7 heads = 448 MiB a layer at T=8192 against the 59
    MiB of float32 shares the sum reads; it would also give the ungrouped
    kernel a scratch accumulator it does not have today. The sum costs 0.4
    ms a layer (4.72 ms against 4.31 for 7 heads on 7; my chip run, PR 31);
    the inside variant was not built, so not measured.

    Under a window the forward and dQ loops start at the first k block the
    q block's first query can see and the dK/dV loop ends at the last q
    block that can see the k block's newest key (`_k_blocks`); every block
    that is computed is masked as before (`_visible`), the edges' and the
    interior's alike, so `window=None` compiles to the kernels it always
    did.

    The latent form (`rope`: the rotary queries [Bq, T, Hq*dr] and the one
    rotary key [Bk, T, dr] all heads share) gives two results more. The
    rotary queries' gradient: a head writes its dr lanes of a block and
    zeros beside them into the slab of its place among the hr heads of that
    block, and the hr slabs are summed after the kernel (16 MiB each at [1,
    4096, 32 x 64] bf16). The shared key's gradient: every head writes a
    float32 share [T, 128], summed after the kernel as a group's shares
    are, and the hr copies the key was repeated to fold back into one.
    Returns (dq, dk, dv, dq_rope, dk_rope) then.

    The two calls are `_flash_bwd_dq_call` and `_flash_bwd_dkdv_call`,
    traced once a shape; the pad, the reshapes, the latent form's repeat
    and the sums after the kernels stay here, under the op's scope."""
    q, k, v, kv_len, out, lse = res
    rows, t, hd = q.shape
    rows_kv, heads_kv = k.shape[0], k.shape[2] // d
    t_pad = _pad_t(t, block_q, block_k)
    pad = [(0, 0), (0, t_pad - t), (0, 0)] if t_pad != t else None
    if pad:
        q, k, v, g, out = (jnp.pad(a, pad) for a in (q, k, v, g, out))
    q, k, v, g, out = (a.reshape(-1, a.shape[2]) for a in (q, k, v, g, out))
    lens = kv_len.reshape(rows, 1).astype(jnp.int32)
    rope_args, rope_static = (), None
    if rope is not None:
        rope_args, rope_static = _rope_rows(rope, d, hb, pad)
    static = dict(rows=rows, d=d, hb=hb, scale=scale, causal=causal,
                  window=window, block_q=block_q, block_k=block_k,
                  rope=rope_static, interpret=interpret, bd=bd)
    dq, delta, *dq_rope = _flash_bwd_dq_call(
        q, g, out, k, v, lse, rope_args, lens, **static)
    dk, dv, *dk_rope = _flash_bwd_dkdv_call(
        q, g, k, v, lse, delta, rope_args, lens, **static)
    dq = dq.reshape(rows, t_pad, hd)
    if q.shape != k.shape:              # grouped: the shares' sum
        dk, dv = (a.reshape(rows_kv, -1, t_pad, heads_kv * d).sum(1)
                  .astype(k.dtype) for a in (dk, dv))
    else:
        dk, dv = (a.reshape(rows_kv, t_pad, -1) for a in (dk, dv))
    grads = (dq, dk, dv)
    if rope is not None:
        dr, hr = rope_static
        dqr = dq_rope[0].reshape(hr, rows, t_pad, -1).sum(0)
        dkr = dk_rope[0].reshape(rows_kv, -1, t_pad, hr, dr).sum((1, 3))
        grads += (dqr, dkr.astype(rope[1].dtype))
    if t_pad != t:
        grads = tuple(a[:, :t] for a in grads)
    return grads


def _wait_for(g, *residuals):
    """(g, *residuals) behind one optimization barrier, for a backward rule
    whose linearization the forward op kept (core/lowering.py). What a
    backward rule first does to its residuals depends on nothing the
    backward pass computes: a [N] -> [N, 1] reshape, which pads one lane to
    128 (8 MiB for layer norm's mean). Left alone, XLA merges each with its
    inverse in the forward rule or runs it as soon as the residual exists,
    and holds the padded array from the forward to the backward pass: +0.5
    GiB (layer norm) on the T=2048 transformer step (AOT compile for a v5e,
    PR 25). Behind the barrier the residuals are held as the forward rule
    saved them until the cotangent `g` exists."""
    return lax.optimization_barrier((g,) + residuals)


def _to_bh(x):
    """[B, T, H, D] -> [B*H, T, D]: every head a row of its own, for the
    shapes whose heads cannot be indexed in place (`heads_a_block` None:
    grouped queries at a D that is no multiple of 128, and heads of a D
    that neither divides 128 nor fills whole blocks)."""
    b, t, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, t, d)


def _from_bh(x, b):
    bh, t, d = x.shape
    return jnp.transpose(x.reshape(b, bh // b, t, d), (0, 2, 1, 3))


def _rows(x, hb):
    """An operand of the op, [B, T, H, D], as the kernels take it: [B, T,
    H*D], a reshape; [B*H, T, D] where its heads cannot be indexed in
    place."""
    return _to_bh(x) if hb is None else x.reshape(x.shape[:2] + (-1,))


def _unrows(x, like, hb):
    """`_rows`'s inverse, to the shape of the op's operand `like`."""
    return _from_bh(x, like.shape[0]) if hb is None else x.reshape(like.shape)


# q, k, v and the output are [B, T, H, D] on both sides of the custom_vjp
# boundary. The kernels take them as [B*T, H*D], a reshape of what the op
# has (both model builders make that very array by a matmul and reshape it
# to heads): a head's blocks are indexed in place, W = D lanes wide where D
# is a multiple of 128 and 128 wide with two heads a block at D=64
# (`heads_a_block`), and no XLA pass stands between the op's operands and
# the pallas_calls, forward or backward. The residuals are the op's own
# inputs and output (live anyway for the ops around it) plus the logsumexp
# as [B*H, nq, 1, block_q] rows, lane-dense as the kernels write and read
# it; delta = rowsum(dO * O) is the dQ kernel's, into rows of the same shape
# (as an XLA reduction its [B, T, H] result has the heads in the lanes, and
# XLA ran it tokens-minor behind three float32 relayouts of dO and O, 220
# MiB a call at [8, 2048, 8, 64]; AOT compile, PR 38). No barrier holds the
# residuals back: the backward rule's first touch of them is a reshape.
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_core(q, k, v, rope, kv_len, scale, causal, window, block_q,
                block_k, interpret, bd=None):
    return _flash_core_fwd(q, k, v, rope, kv_len, scale, causal, window,
                           block_q, block_k, interpret, bd)[0]


def _flash_layout(q, k, kv_len):
    """(heads a block, or None for the kernels' rows a head; the key
    lengths a kernel row)."""
    hb = heads_a_block(q.shape[2], k.shape[2], q.shape[3])
    return hb, kv_len if hb else jnp.repeat(kv_len, q.shape[2])


def _rope_flat(rope):
    """The latent form's (q_rope [B, T, Hq, dr], k_rope [B, T, 1, dr]) as
    rows of lanes, or None."""
    return None if rope is None else tuple(
        a.reshape(a.shape[:2] + (-1,)) for a in rope)


def _flash_core_fwd(q, k, v, rope, kv_len, scale, causal, window, block_q,
                    block_k, interpret, bd=None):
    hb, lens = _flash_layout(q, k, kv_len)
    out, lse = _flash_fwd(_rows(q, hb), _rows(k, hb), _rows(v, hb), lens,
                          q.shape[3], hb or 1, scale, causal, window,
                          block_q, block_k, interpret, _rope_flat(rope), bd)
    out = _unrows(out, q, hb)
    return out, (q, k, v, rope, kv_len, out, lse)


def _flash_core_bwd(scale, causal, window, block_q, block_k, interpret, bd,
                    res, g):
    q, k, v, rope, kv_len, out, lse = res
    hb, lens = _flash_layout(q, k, kv_len)
    dq, dk, dv, *drope = _flash_bwd(
        q.shape[3], hb or 1, scale, causal, window, block_q, block_k,
        interpret, (_rows(q, hb), _rows(k, hb), _rows(v, hb), lens,
                    _rows(out, hb), lse), _rows(g, hb), _rope_flat(rope), bd)
    drope = tuple(d.reshape(a.shape) for d, a in zip(drope, rope)) \
        if rope is not None else None
    return _unrows(dq, q, hb), _unrows(dk, k, hb), _unrows(dv, v, hb), \
        drope, None


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def latent_form(d, dr, dv):
    """The form the flash kernels run a latent head [D without position; dr
    rotary] on a value of dv in, by its widths alone: "two_part" where D is
    the value's width (128 + 64 on 128: the two products taken apart in the
    kernels, nothing joined or repeated in HBM); "whole" where the whole
    head is (192 + 64 on 256: q = [q; q_rope] and k = [k; k_rope repeated a
    head] joined in HBM for the plain kernels at that width, dk_rope the
    sum over heads that the repeat's transpose takes after the kernel).
    Padding the part without position to the value's width for the
    two-part kernels lost to the join by 22 % forward and 27 % forward +
    backward on the v5e at 20 heads and T=4096 (PERF.md section 6, PR 49)
    and is not built. Any other widths: a ValueError."""
    if d == dv:
        return "two_part"
    if d + dr == dv:
        return "whole"
    raise ValueError(
        "flash_attention: the latent form takes a part without position as "
        "wide as the value (D + dr on D, D a multiple of 128: 128 + 64 on "
        "128) or a whole head as wide as the value (D + dr on dv = D + dr: "
        "192 + 64 on 256); got %d + %d on %d" % (d, dr, dv))


def flash_attention(q, k, v, causal=False, scale=None, kv_len=None,
                    block_q=None, block_k=None, interpret=None, window=None,
                    q_rope=None, k_rope=None, block_diffusion=None):
    """Exact attention, flash-style. q: [B, T, Hq, D], k, v: [B, T, Hkv, D]
    (BTHD, the layout ring_attention uses); returns [B, T, Hq, D]. block_q /
    block_k default to kernel_config.DEFAULT_TILES["attn"] and are clamped
    to T. The widths it takes: q, k and v of one width D, any; and, the
    latent form, a head of D + dr on keys of D + dr and values of D (128 +
    64 on 128) or of another width dv (192 + 64 on 256; the result is then
    [B, T, Hq, dv]), given as its two parts (below).

    The latent form (q_rope [B, T, Hq, dr] and k_rope [B, T, 1, dr], both
    or neither): a head's score is q . k + q_rope . k_rope, the second key
    one that every head reads, as in latent attention where a head is [a
    part without position (D); a rotary part (dr)] and its value is D wide.
    The two products are taken apart in the kernels: nothing is
    concatenated, and the shared key is not repeated a head, in HBM. D must
    be a multiple of 128 and dr divide 128 (or be a multiple of it), with
    as many key/value heads as query heads; `scale` is the caller's (the
    default is 1 / sqrt(D + dr)). dq_rope and dk_rope come back in their
    operands' shapes, dk_rope summed over the heads. Where the value is not
    D wide but D + dr (192 + 64 on 256) the head runs whole (`latent_form`):
    the two parts joined here, the plain kernels at the value's width.

    Grouped queries come from the shapes: Hq a multiple of Hkv, and query
    head h reads key/value head h // (Hq // Hkv); the kernels find that
    head's blocks by their index maps, and no copy of K or V is repeated in
    HBM. dk and dv come back [B, T, Hkv, D], summed over each group.

    window: None, or a static int: query i sees key j only where i - j <
    window, so with `causal` the `window` newest keys up to itself (the
    Hugging Face sliding-window mask's convention). K blocks wholly older
    than the window are skipped like those past the causal frontier, in
    all three kernels; a window of T or more changes nothing but the loop
    bounds' arithmetic.

    block_diffusion: None, or two static ints (block_length, L): the T = 2 L
    rows are two copies of one sequence of L tokens, the noised copy in rows
    0 .. L - 1 and the clean copy in rows L .. 2 L - 1 (BD3-LM,
    arXiv:2503.09573), block_length dividing L. Row r is (copy, position i,
    block b = i // block_length) and sees row s iff: both noised and b_s =
    b_r (block-diagonal, both directions inside a block); or r noised, s
    clean and b_s < b_r (offset block-causal); or both clean and b_s <= b_r
    (block-causal); a clean row never sees a noised one. No key block
    without a visible pair is streamed, in any of the three kernels: a block
    of queries meets the two ranges of `_bd_blocks` (its own diagonal on the
    noised copy, the clean keys up to its frontier), the dK/dV kernel their
    transpose. With `causal`, a window, kv_len or the latent form it is
    refused: the mask is all there is.

    kv_len: optional [B] int true key lengths — keys at position >= kv_len
    are masked out AND their blocks skipped entirely (the padded-batch
    regime every fluid sequence model runs in). Differentiable. On TPU the
    forward and both backward kernels compile through Mosaic (online
    softmax in VMEM); off-TPU the same bodies run in interpret mode.

    Precision follows the inputs' dtype, the one thing the kernels look at:
    float32 q, k, v run every dot on float32 operands and match
    attention_reference to float32 tolerance in the interpreter (2e-4
    forward, 2e-3 gradients; on the TPU a float32 dot at default precision
    is one bf16 pass of the MXU, as XLA's own are); bf16 q, k, v (and dO)
    go to the MXU as bf16 with float32 accumulation, the softmax, its
    statistics and every accumulator stay float32, and p and ds are rounded
    to bf16 where they enter a dot: within 0.5 % of the largest value of
    the float32 result on the same rounded inputs, forward and gradients,
    which is closer than the dense path gets under bf16 AMP (its logits and
    softmax are bf16 too).
    """
    if interpret is None:
        interpret = _interpret_default()
    b, t, h, d = q.shape
    latent = q_rope is not None or k_rope is not None
    if k.shape[:3] != v.shape[:3] or h % k.shape[2] or k.shape[3] != d \
            or not latent and v.shape[3] != d:
        raise ValueError(
            "flash_attention: q %s needs k and v alike, [B, T, Hkv, D] at q's "
            "own width D with Hkv dividing the query heads (a head whose "
            "keys are wider than its values, 128 + 64 on 128, or whose part "
            "without position is narrower, 192 + 64 on 256, gives the "
            "rotary width as q_rope and k_rope); got k %s, v %s"
            % (q.shape, k.shape, v.shape))
    rope = None
    if latent:
        if q_rope is None or k_rope is None or k.shape[2] != h \
                or q_rope.shape[:3] != q.shape[:3] \
                or k_rope.shape != (b, t, 1, q_rope.shape[3]):
            raise ValueError(
                "flash_attention: the latent form takes q_rope [B, T, Hq, "
                "dr] and k_rope [B, T, 1, dr] together, on as many key/value "
                "heads as query heads; got q %s, k %s, q_rope %s, k_rope %s"
                % (q.shape, k.shape, getattr(q_rope, "shape", None),
                   getattr(k_rope, "shape", None)))
        dr, dv = q_rope.shape[3], v.shape[3]
        if scale is None:
            scale = 1.0 / float(np.sqrt(d + dr))
        if latent_form(d, dr, dv) == "two_part":
            rope = (q_rope, k_rope)
        else:
            # differentiated by jax around the kernels' own rule: the
            # repeat's transpose sums dk_rope over the heads
            q = jnp.concatenate([q, q_rope], -1)
            k = jnp.concatenate(
                [k, jnp.broadcast_to(k_rope, q_rope.shape)], -1)
    if window is not None and int(window) < 1:
        raise ValueError("flash_attention: window must be None or >= 1, got "
                         "%r" % (window,))
    if block_diffusion is not None:
        block_diffusion = tuple(int(n) for n in block_diffusion)
        length, copy = block_diffusion
        if causal or window is not None or kv_len is not None or latent:
            raise ValueError(
                "flash_attention: block_diffusion is the whole mask: causal, "
                "window, kv_len and the latent form are refused beside it")
        if length < 1 or copy % length or t != 2 * copy:
            raise ValueError(
                "flash_attention: block_diffusion (block_length, L) takes "
                "rows of two copies of L tokens, T = 2 L, in whole blocks; "
                "got %r at T = %d" % (block_diffusion, t))
    if scale is None:
        scale = 1.0 / float(np.sqrt(d))
    block_q = max(8, min(_tile("attn", "block_q", block_q),
                         int(-(-t // 8) * 8)))
    block_k = max(8, min(_tile("attn", "block_k", block_k),
                         int(-(-t // 8) * 8)))
    if kv_len is None:
        lens = jnp.full((b,), t, jnp.int32)
    else:
        lens = jnp.asarray(kv_len, jnp.int32).reshape(b)
    return _flash_core(q, k, v, rope, lens, float(scale), bool(causal),
                       None if window is None else int(window),
                       int(block_q), int(block_k), bool(interpret),
                       block_diffusion)


# ---------------------------------------------------------------------------
# fused softmax + cross-entropy
# ---------------------------------------------------------------------------

def _xent_kernel(logits_ref, labels_ref, loss_ref, lse_ref):
    x = logits_ref[:].astype(jnp.float32)                    # [bn, V]
    lab = labels_ref[:]                                      # [bn, 1] int32
    m = jnp.max(x, axis=-1, keepdims=True)
    lse = m + jnp.log(jnp.sum(jnp.exp(x - m), axis=-1, keepdims=True))
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    picked = jnp.sum(jnp.where(cols == lab, x, 0.0), axis=-1,
                     keepdims=True)
    loss_ref[:] = lse - picked
    lse_ref[:] = lse


def _xent_rows(logits, block_n):
    """Rows a grid step of the kernel takes: what the caller names, else
    DEFAULT_TILES["xent"]'s bytes as layer_norm turns its own into rows (a
    float32 working copy of one tile; 16 rows of a 2-byte dtype at every
    vocabulary the cells have)."""
    if block_n is not None:
        return int(block_n)
    n, v = logits.shape
    return _ln_block_rows(n, v, logits.dtype,
                          DEFAULT_TILES["xent"]["tile_bytes"])


def _xent_params(block_n, v, itemsize):
    """Mosaic's scoped-VMEM limit for the kernel: two buffers of a
    [block_n, V] block in the logits' dtype and room for the float32
    working copies of one block; never under the default 16 MiB. A row of
    151936 logits is 0.6 MiB in float32, and sixteen of them with their
    temporaries pass the default."""
    need = block_n * v * (2 * itemsize + 6 * 4)
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=int(min(max(need, 16 << 20), 100 << 20)))


@kernel_entry("ptpu_softmax_xent_fwd",
              static_argnames=("block_n", "interpret"))
def _xent_call(logits, labels, *, block_n, interpret):
    """(loss, lse) [N, 1] of logits [N, V] and labels [N, 1] int32, block_n
    rows a grid step."""
    # no pad: the last block of a grid that does not divide N reads rows
    # past the end (unspecified values, each row's own) and its writes there
    # are dropped
    n, v = logits.shape
    row = lambda i: (i, 0)
    return pl.pallas_call(
        _xent_kernel,
        grid=(pl.cdiv(n, block_n),),
        in_specs=[
            _vmem_spec((block_n, v), row),
            _vmem_spec((block_n, 1), row),
        ],
        out_specs=[
            _vmem_spec((block_n, 1), row),
            _vmem_spec((block_n, 1), row),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        compiler_params=_xent_params(block_n, v, logits.dtype.itemsize),
        interpret=interpret,
        name="ptpu_softmax_xent_fwd",
    )(logits, labels)


def _xent_fwd_call(logits, labels, block_n, interpret):
    return _xent_call(logits, labels.reshape(-1, 1).astype(jnp.int32),
                      block_n=_xent_rows(logits, block_n),
                      interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _xent_core(logits, labels, block_n, interpret):
    loss, _ = _xent_fwd_call(logits, labels, block_n, interpret)
    return loss


def _xent_core_fwd(logits, labels, block_n, interpret):
    loss, lse = _xent_fwd_call(logits, labels, block_n, interpret)
    return loss, (logits, labels, lse)


def _xent_core_bwd(block_n, interpret, res, g):
    # jax.numpy, so that XLA computes it on the way into the two matmuls
    # that read dlogits (float32, rounded once to the logits' dtype). A
    # kernel that wrote dlogits once for both lost to this in every cell
    # (PERF.md section 6, PR 44): the exponentials hide under the MXU's
    # time, a finished [N, V] array costs a read and a write in HBM
    logits, labels, lse = res
    p = jnp.exp(logits.astype(jnp.float32) - lse)            # softmax
    onehot = jax.nn.one_hot(labels.reshape(-1), logits.shape[-1],
                            dtype=jnp.float32)
    dlogits = (p - onehot) * g.reshape(-1, 1)
    return dlogits.astype(logits.dtype), None


_xent_core.defvjp(_xent_core_fwd, _xent_core_bwd)


def softmax_xent(logits, labels, block_n=None, interpret=None):
    """Fused log-softmax + NLL. logits [N, V] in any float dtype (a tile is
    cast to float32 in VMEM), labels [N] (or [N,1]) int. Returns loss [N, 1]
    float32. Differentiable (custom_vjp); dlogits comes in the logits'
    dtype."""
    if interpret is None:
        interpret = _interpret_default()
    return _xent_core(logits, labels.reshape(-1),
                      None if block_n is None else int(block_n),
                      bool(interpret))


# ---------------------------------------------------------------------------
# fused layer norm
# ---------------------------------------------------------------------------

def _ln_kernel(x_ref, scale_ref, bias_ref, y_ref, mean_ref, rstd_ref, *,
               eps):
    x = x_ref[:].astype(jnp.float32)                         # [bn, D]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    y = (x - mu) * rstd * scale_ref[:].astype(jnp.float32) \
        + bias_ref[:].astype(jnp.float32)
    y_ref[:] = y.astype(y_ref.dtype)
    mean_ref[:] = mu
    rstd_ref[:] = rstd


def _ln_block_rows(n, d, dtype, tile_bytes):
    """Rows of x [n, d] one grid step of the layer_norm kernel takes, from
    a budget in bytes for the float32 working copy of one input tile (the
    kernel casts to float32 whatever `dtype` is). A multiple of the
    dtype's sublane granule (8 rows, 16 for a 2-byte dtype), at least one
    granule however wide a row is, at most n rounded up to one. Where a
    multiple that divides n lies within a factor of two below, that one:
    the call then adds no pad before the kernel and no slice after it."""
    granule = 8 * max(1, 4 // jnp.dtype(dtype).itemsize)
    rows = max(granule, tile_bytes // (d * 4) // granule * granule)
    rows = min(rows, -(-n // granule) * granule)
    for fit in range(rows, rows // 2, -granule):
        if n % fit == 0:
            return fit
    return rows


@kernel_entry("ptpu_layer_norm_fwd",
              static_argnames=("eps", "block_n", "interpret"))
def _ln_call(x, scale, bias, *, eps, block_n, interpret):
    """(y, mean [N, 1], rstd [N, 1]) of x [N, D], N whole blocks of block_n
    rows, under scale and bias [1, D]."""
    n_pad, d = x.shape
    return pl.pallas_call(
        functools.partial(_ln_kernel, eps=eps),
        grid=(n_pad // block_n,),
        in_specs=[
            _vmem_spec((block_n, d), lambda i: (i, 0)),
            _vmem_spec((1, d), lambda i: (0, 0)),
            _vmem_spec((1, d), lambda i: (0, 0)),
        ],
        out_specs=[
            _vmem_spec((block_n, d), lambda i: (i, 0)),
            _vmem_spec((block_n, 1), lambda i: (i, 0)),
            _vmem_spec((block_n, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, d), x.dtype),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ],
        interpret=interpret,
        name="ptpu_layer_norm_fwd",
    )(x, scale, bias)


def _ln_fwd_call(x, scale, bias, eps, block_n, interpret):
    n, d = x.shape
    if block_n is None:
        block_n = _ln_block_rows(n, d, x.dtype,
                                 DEFAULT_TILES["ln"]["tile_bytes"])
    n_pad = int(-(-n // block_n) * block_n)
    xp = jnp.pad(x, [(0, n_pad - n), (0, 0)]) if n_pad != n else x
    y, mean, rstd = _ln_call(xp, scale.reshape(1, d), bias.reshape(1, d),
                             eps=eps, block_n=block_n, interpret=interpret)
    return y[:n], mean[:n], rstd[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ln_core(x, scale, bias, eps, block_n, interpret):
    y, _, _ = _ln_fwd_call(x, scale, bias, eps, block_n, interpret)
    return y


def _ln_core_fwd(x, scale, bias, eps, block_n, interpret):
    y, mean, rstd = _ln_fwd_call(x, scale, bias, eps, block_n, interpret)
    # residuals must be jax values: a 0-size sentinel carries bias's dtype
    return y, (x, scale, jnp.zeros((0,), bias.dtype), mean.reshape(-1),
               rstd.reshape(-1))


def _ln_core_bwd(eps, block_n, interpret, res, g):
    x, scale, bias_like, mean, rstd = res
    g, mean, rstd = _wait_for(g, mean, rstd)
    mean, rstd = mean.reshape(-1, 1), rstd.reshape(-1, 1)
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    xhat = (xf - mean) * rstd                                # [N, D]
    gs = gf * scale.reshape(1, -1).astype(jnp.float32)
    dx = rstd * (gs - jnp.mean(gs, axis=-1, keepdims=True)
                 - xhat * jnp.mean(gs * xhat, axis=-1, keepdims=True))
    dscale = jnp.sum(gf * xhat, axis=0)
    dbias = jnp.sum(gf, axis=0)
    return (dx.astype(x.dtype), dscale.astype(scale.dtype),
            dbias.astype(bias_like.dtype))


_ln_core.defvjp(_ln_core_fwd, _ln_core_bwd)


def _pad_rows(a, rows):
    if a.shape[0] == rows:
        return a
    return jnp.pad(a, [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


def _resolve_block_b(b, block_b):
    """(block, padded_b) for a batch-blocked kernel. block_b=0 = the
    whole batch in one block; both forms pad b up to a multiple of 8 (the
    f32 sublane tile)."""
    if block_b > 0:
        blk = max(8, block_b)
    else:
        blk = int(-(-b // 8) * 8)
    return blk, int(-(-b // blk) * blk)


# ---------------------------------------------------------------------------
# fused LSTM recurrence (reference: lstm_op.cc / lstmp_op.cc — a host loop
# calling cuBLAS per step; here ONE pallas kernel walks the whole sequence:
# grid (batch-block, T), carried (h, c) state resident in VMEM scratch, the
# four gates + state update one VMEM pass per step, @SEQLEN-masked carries)
# ---------------------------------------------------------------------------

def _lstm_seq_kernel(x_ref, m_ref, w_ref, b_ref, h0_ref, c0_ref,
                     h_out, c_out, h_scr, c_scr, *, d):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    h_prev = h_scr[:]
    c_prev = c_scr[:]
    xt = x_ref[0].astype(jnp.float32)                       # [bb, 4D]
    gates = xt + jnp.dot(h_prev, w_ref[:],
                         preferred_element_type=jnp.float32) + b_ref[0]
    # reference gate order lstm_op.cc:125 {W_ch, W_ih, W_fh, W_oh}:
    # candidate block FIRST
    z = jnp.tanh(gates[:, :d])
    i = jax.nn.sigmoid(gates[:, d:2 * d])
    f = jax.nn.sigmoid(gates[:, 2 * d:3 * d])
    o = jax.nn.sigmoid(gates[:, 3 * d:])
    c_new = f * c_prev + i * z
    h_new = o * jnp.tanh(c_new)
    mt = m_ref[0]                                           # [bb, 1]
    h = mt * h_new + (1 - mt) * h_prev
    c = mt * c_new + (1 - mt) * c_prev
    h_scr[:] = h
    c_scr[:] = c
    h_out[0] = h.astype(h_out.dtype)
    c_out[0] = c.astype(c_out.dtype)


@kernel_entry("ptpu_lstm_seq", static_argnames=("blk", "interpret"))
def _lstm_call(xs, ms, w, b, h0, c0, *, blk, interpret):
    """`_lstm_fwd_call` on a batch of whole blocks of blk rows, b [1, 4D]."""
    t, b_pad, four_d = xs.shape
    d = four_d // 4
    return pl.pallas_call(
        functools.partial(_lstm_seq_kernel, d=d),
        # batch blocks on the MAJOR grid axis: each block walks its
        # full time loop before the next block reuses the state scratch
        grid=(b_pad // blk, t),
        in_specs=[
            _vmem_spec((1, blk, four_d), lambda bb, i: (i, bb, 0)),
            _vmem_spec((1, blk, 1), lambda bb, i: (i, bb, 0)),
            _vmem_spec((d, four_d), lambda bb, i: (0, 0)),
            _vmem_spec((1, four_d), lambda bb, i: (0, 0)),
            _vmem_spec((blk, d), lambda bb, i: (bb, 0)),
            _vmem_spec((blk, d), lambda bb, i: (bb, 0)),
        ],
        out_specs=[
            _vmem_spec((1, blk, d), lambda bb, i: (i, bb, 0)),
            _vmem_spec((1, blk, d), lambda bb, i: (i, bb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b_pad, d), jnp.float32),
            jax.ShapeDtypeStruct((t, b_pad, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, d), jnp.float32),
            pltpu.VMEM((blk, d), jnp.float32),
        ],
        interpret=interpret,
        name="ptpu_lstm_seq",
    )(xs, ms, w, b, h0, c0)


def _lstm_fwd_call(xs, ms, w, b, h0, c0, block_b, interpret):
    """xs [T, B, 4D] f32, ms [T, B, 1], w [D, 4D], b [4D], h0/c0 [B, D]
    -> (hs, cs) [T, B, D]."""
    bsz = xs.shape[1]
    blk, b_pad = _resolve_block_b(bsz, block_b)
    if b_pad != bsz:
        xs = jnp.pad(xs, [(0, 0), (0, b_pad - bsz), (0, 0)])
        ms = jnp.pad(ms, [(0, 0), (0, b_pad - bsz), (0, 0)])
        h0 = _pad_rows(h0, b_pad)
        c0 = _pad_rows(c0, b_pad)
    hs, cs = _lstm_call(xs, ms, w, b.reshape(1, -1), h0, c0, blk=blk,
                        interpret=interpret)
    return hs[:, :bsz], cs[:, :bsz]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _lstm_seq_core(xs, ms, w, b, h0, c0, block_b, interpret):
    hs, cs = _lstm_fwd_call(xs, ms, w, b, h0, c0, block_b, interpret)
    return hs, cs


def _lstm_seq_core_fwd(xs, ms, w, b, h0, c0, block_b, interpret):
    hs, cs = _lstm_fwd_call(xs, ms, w, b, h0, c0, block_b, interpret)
    return (hs, cs), (xs, ms, w, b, h0, c0, hs, cs)


def _lstm_seq_core_bwd(block_b, interpret, res, g):
    """Exact reverse-mode through the recurrence from the SAVED states
    (no forward recompute): one reverse scan, each step re-deriving the
    gates from (h_{t-1}, c_{t-1}) with one matmul, then the standard
    LSTM chain rule. Matches jax.grad of the unfused lax.scan path
    (regression-tested)."""
    xs, ms, w, b, h0, c0, hs, cs = res
    ghs, gcs = g
    d = w.shape[0]
    h_prevs = jnp.concatenate([h0[None], hs[:-1]], axis=0)   # [T, B, D]
    c_prevs = jnp.concatenate([c0[None], cs[:-1]], axis=0)

    def step(carry, inp):
        dh_c, dc_c, dw, db = carry
        xt, mt, h_prev, c_prev, gh, gc_out = inp
        dh = dh_c + gh
        dc = dc_c + gc_out
        gates = xt + h_prev @ w + b
        z = jnp.tanh(gates[:, :d])
        i = jax.nn.sigmoid(gates[:, d:2 * d])
        f = jax.nn.sigmoid(gates[:, 2 * d:3 * d])
        o = jax.nn.sigmoid(gates[:, 3 * d:])
        c_new = f * c_prev + i * z
        tc = jnp.tanh(c_new)
        dh_new = dh * mt
        dc_new = dc * mt + dh_new * o * (1 - tc * tc)
        dgo = dh_new * tc * o * (1 - o)
        dgf = dc_new * c_prev * f * (1 - f)
        dgi = dc_new * z * i * (1 - i)
        dgc = dc_new * i * (1 - z * z)
        dg = jnp.concatenate([dgc, dgi, dgf, dgo], axis=-1)  # [B, 4D]
        dw = dw + h_prev.T @ dg
        db = db + jnp.sum(dg, axis=0)
        dh_prev = dg @ w.T + dh * (1 - mt)
        dc_prev = dc_new * f + dc * (1 - mt)
        return (dh_prev, dc_prev, dw, db), dg

    init = (jnp.zeros_like(h0), jnp.zeros_like(c0),
            jnp.zeros_like(w), jnp.zeros_like(b))
    (dh0, dc0, dw, db), dxs = lax.scan(
        step, init, (xs, ms, h_prevs, c_prevs, ghs, gcs), reverse=True)
    return dxs, jnp.zeros_like(ms), dw, db, dh0, dc0


_lstm_seq_core.defvjp(_lstm_seq_core_fwd, _lstm_seq_core_bwd)


def fused_lstm(x, w, gate_bias, h0, c0, xlen, reverse=False, block_b=None,
               interpret=None):
    """Fused-gate dynamic LSTM over the padded-dense layout: x [B, T, 4D]
    (pre-projected gate inputs), w [D, 4D] recurrent weight, gate_bias
    [4D]; returns (hidden, cell) [B, T, D] in x's dtype. Default
    activations only (sigmoid gates, tanh candidate/cell — the
    dispatching op falls back to the lax.scan path otherwise), @SEQLEN
    masking via xlen [B] (padding steps carry state through),
    differentiable (custom_vjp; saved-state reverse scan backward), and
    runs the same kernel in interpret mode off-TPU."""
    if interpret is None:
        interpret = _interpret_default()
    b, t, four_d = x.shape
    d = four_d // 4
    xs = jnp.swapaxes(x, 0, 1).astype(jnp.float32)           # [T, B, 4D]
    lens = jnp.asarray(xlen, jnp.int32)
    mask = (lax.broadcasted_iota(jnp.int32, (t, b), 0)
            < lens[None, :]).astype(jnp.float32)[:, :, None]  # [T, B, 1]
    if reverse:
        xs = xs[::-1]
        mask = mask[::-1]
    h0 = jnp.zeros((b, d), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    c0 = jnp.zeros((b, d), jnp.float32) if c0 is None \
        else c0.astype(jnp.float32)
    hs, cs = _lstm_seq_core(xs, mask, w.astype(jnp.float32),
                            gate_bias.reshape(-1).astype(jnp.float32),
                            h0, c0, _tile("lstm", "block_b", block_b),
                            bool(interpret))
    if reverse:
        hs, cs = hs[::-1], cs[::-1]
    return (jnp.swapaxes(hs, 0, 1).astype(x.dtype),
            jnp.swapaxes(cs, 0, 1).astype(x.dtype))


# --- lstmp: recurrent projection (the [B, P] projected state feeds the
# next step's gate matmul; see ops/sequence_ops._lstmp for the layout) ---

def _lstmp_seq_kernel(x_ref, m_ref, w_ref, wp_ref, b_ref, r0_ref, c0_ref,
                      r_out, c_out, r_scr, c_scr, *, d):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        r_scr[:] = r0_ref[:].astype(jnp.float32)
        c_scr[:] = c0_ref[:].astype(jnp.float32)

    r_prev = r_scr[:]
    c_prev = c_scr[:]
    xt = x_ref[0].astype(jnp.float32)                       # [bb, 4D]
    gates = xt + jnp.dot(r_prev, w_ref[:],
                         preferred_element_type=jnp.float32) + b_ref[0]
    z = jnp.tanh(gates[:, :d])
    i = jax.nn.sigmoid(gates[:, d:2 * d])
    f = jax.nn.sigmoid(gates[:, 2 * d:3 * d])
    o = jax.nn.sigmoid(gates[:, 3 * d:])
    c_new = f * c_prev + i * z
    h_new = o * jnp.tanh(c_new)
    r_new = jnp.tanh(jnp.dot(h_new, wp_ref[:],
                             preferred_element_type=jnp.float32))
    mt = m_ref[0]
    r = mt * r_new + (1 - mt) * r_prev
    c = mt * c_new + (1 - mt) * c_prev
    r_scr[:] = r
    c_scr[:] = c
    r_out[0] = r.astype(r_out.dtype)
    c_out[0] = c.astype(c_out.dtype)


@kernel_entry("ptpu_lstmp_seq", static_argnames=("blk", "interpret"))
def _lstmp_call(xs, ms, w, w_proj, b, r0, c0, *, blk, interpret):
    """`_lstmp_fwd_call` on a batch of whole blocks of blk rows, b [1,
    4D]."""
    t, b_pad, four_d = xs.shape
    d = four_d // 4
    p = w_proj.shape[1]
    return pl.pallas_call(
        functools.partial(_lstmp_seq_kernel, d=d),
        grid=(b_pad // blk, t),
        in_specs=[
            _vmem_spec((1, blk, four_d), lambda bb, i: (i, bb, 0)),
            _vmem_spec((1, blk, 1), lambda bb, i: (i, bb, 0)),
            _vmem_spec((p, four_d), lambda bb, i: (0, 0)),
            _vmem_spec((d, p), lambda bb, i: (0, 0)),
            _vmem_spec((1, four_d), lambda bb, i: (0, 0)),
            _vmem_spec((blk, p), lambda bb, i: (bb, 0)),
            _vmem_spec((blk, d), lambda bb, i: (bb, 0)),
        ],
        out_specs=[
            _vmem_spec((1, blk, p), lambda bb, i: (i, bb, 0)),
            _vmem_spec((1, blk, d), lambda bb, i: (i, bb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((t, b_pad, p), jnp.float32),
            jax.ShapeDtypeStruct((t, b_pad, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk, p), jnp.float32),
            pltpu.VMEM((blk, d), jnp.float32),
        ],
        interpret=interpret,
        name="ptpu_lstmp_seq",
    )(xs, ms, w, w_proj, b, r0, c0)


def _lstmp_fwd_call(xs, ms, w, w_proj, b, r0, c0, block_b, interpret):
    bsz = xs.shape[1]
    blk, b_pad = _resolve_block_b(bsz, block_b)
    if b_pad != bsz:
        xs = jnp.pad(xs, [(0, 0), (0, b_pad - bsz), (0, 0)])
        ms = jnp.pad(ms, [(0, 0), (0, b_pad - bsz), (0, 0)])
        r0 = _pad_rows(r0, b_pad)
        c0 = _pad_rows(c0, b_pad)
    rs, cs = _lstmp_call(xs, ms, w, w_proj, b.reshape(1, -1), r0, c0,
                         blk=blk, interpret=interpret)
    return rs[:, :bsz], cs[:, :bsz]


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _lstmp_seq_core(xs, ms, w, w_proj, b, r0, c0, block_b, interpret):
    return _lstmp_fwd_call(xs, ms, w, w_proj, b, r0, c0, block_b,
                           interpret)


def _lstmp_seq_core_fwd(xs, ms, w, w_proj, b, r0, c0, block_b, interpret):
    rs, cs = _lstmp_fwd_call(xs, ms, w, w_proj, b, r0, c0, block_b,
                             interpret)
    return (rs, cs), (xs, ms, w, w_proj, b, r0, c0, rs, cs)


def _lstmp_seq_core_bwd(block_b, interpret, res, g):
    xs, ms, w, w_proj, b, r0, c0, rs, cs = res
    grs, gcs = g
    d = w_proj.shape[0]
    r_prevs = jnp.concatenate([r0[None], rs[:-1]], axis=0)
    c_prevs = jnp.concatenate([c0[None], cs[:-1]], axis=0)

    def step(carry, inp):
        dr_c, dc_c, dw, dwp, db = carry
        xt, mt, r_prev, c_prev, gr, gc_out = inp
        dr = dr_c + gr
        dc = dc_c + gc_out
        gates = xt + r_prev @ w + b
        z = jnp.tanh(gates[:, :d])
        i = jax.nn.sigmoid(gates[:, d:2 * d])
        f = jax.nn.sigmoid(gates[:, 2 * d:3 * d])
        o = jax.nn.sigmoid(gates[:, 3 * d:])
        c_new = f * c_prev + i * z
        tc = jnp.tanh(c_new)
        h_new = o * tc
        r_new = jnp.tanh(h_new @ w_proj)
        dr_new = dr * mt
        dproj = dr_new * (1 - r_new * r_new)                 # [B, P]
        dh_new = dproj @ w_proj.T
        dwp = dwp + h_new.T @ dproj
        dc_new = dc * mt + dh_new * o * (1 - tc * tc)
        dgo = dh_new * tc * o * (1 - o)
        dgf = dc_new * c_prev * f * (1 - f)
        dgi = dc_new * z * i * (1 - i)
        dgc = dc_new * i * (1 - z * z)
        dg = jnp.concatenate([dgc, dgi, dgf, dgo], axis=-1)
        dw = dw + r_prev.T @ dg
        db = db + jnp.sum(dg, axis=0)
        dr_prev = dg @ w.T + dr * (1 - mt)
        dc_prev = dc_new * f + dc * (1 - mt)
        return (dr_prev, dc_prev, dw, dwp, db), dg

    init = (jnp.zeros_like(r0), jnp.zeros_like(c0), jnp.zeros_like(w),
            jnp.zeros_like(w_proj), jnp.zeros_like(b))
    (dr0, dc0, dw, dwp, db), dxs = lax.scan(
        step, init, (xs, ms, r_prevs, c_prevs, grs, gcs), reverse=True)
    return dxs, jnp.zeros_like(ms), dw, dwp, db, dr0, dc0


_lstmp_seq_core.defvjp(_lstmp_seq_core_fwd, _lstmp_seq_core_bwd)


def fused_lstmp(x, w, w_proj, gate_bias, r0, c0, xlen, reverse=False,
                block_b=None, interpret=None):
    """Fused LSTMP (recurrent projection): x [B, T, 4D], w [P, 4D],
    w_proj [D, P], r0 [B, P] the PROJECTED initial state (the caller
    projects h0 — its grads flow through that projection's own vjp),
    c0 [B, D]. Returns (projection, cell) = ([B, T, P], [B, T, D]).
    Default activations only, like fused_lstm."""
    if interpret is None:
        interpret = _interpret_default()
    b, t, four_d = x.shape
    d = w_proj.shape[0]
    xs = jnp.swapaxes(x, 0, 1).astype(jnp.float32)
    lens = jnp.asarray(xlen, jnp.int32)
    mask = (lax.broadcasted_iota(jnp.int32, (t, b), 0)
            < lens[None, :]).astype(jnp.float32)[:, :, None]
    if reverse:
        xs = xs[::-1]
        mask = mask[::-1]
    c0 = jnp.zeros((b, d), jnp.float32) if c0 is None \
        else c0.astype(jnp.float32)
    rs, cs = _lstmp_seq_core(xs, mask, w.astype(jnp.float32),
                             w_proj.astype(jnp.float32),
                             gate_bias.reshape(-1).astype(jnp.float32),
                             r0.astype(jnp.float32), c0,
                             _tile("lstm", "block_b", block_b),
                             bool(interpret))
    if reverse:
        rs, cs = rs[::-1], cs[::-1]
    return (jnp.swapaxes(rs, 0, 1).astype(x.dtype),
            jnp.swapaxes(cs, 0, 1).astype(x.dtype))


# ---------------------------------------------------------------------------
# masked sequence softmax / pool (the @SEQLEN-dominated sequence ops: one
# VMEM pass computes mask + reduce + normalize per row block, instead of
# the where/softmax/mul chain XLA materializes between HBM round-trips)
# ---------------------------------------------------------------------------

def _masked_softmax_kernel(x_ref, len_ref, y_ref):
    x = x_ref[:].astype(jnp.float32)                         # [bn, T]
    lens = len_ref[:]                                        # [bn, 1] int32
    cols = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = cols < lens
    s = jnp.where(valid, x, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)
    denom = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    y_ref[:] = (p / denom).astype(y_ref.dtype)


@kernel_entry("ptpu_masked_softmax",
              static_argnames=("block_n", "interpret"))
def _masked_softmax_rows(x, lens, *, block_n, interpret):
    """`_masked_softmax_call` on whole blocks of block_n rows, lens [N, 1]
    int32."""
    n_pad, t = x.shape
    return pl.pallas_call(
        _masked_softmax_kernel,
        grid=(n_pad // block_n,),
        in_specs=[
            _vmem_spec((block_n, t), lambda i: (i, 0)),
            _vmem_spec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=_vmem_spec((block_n, t), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, t), x.dtype),
        interpret=interpret,
        name="ptpu_masked_softmax",
    )(x, lens)


def _masked_softmax_call(x, lens, block_n, interpret):
    n = x.shape[0]
    n_pad = int(-(-n // block_n) * block_n)
    return _masked_softmax_rows(
        _pad_rows(x, n_pad),
        _pad_rows(lens.reshape(-1, 1).astype(jnp.int32), n_pad),
        block_n=block_n, interpret=interpret)[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _masked_softmax_core(x, lens, block_n, interpret):
    return _masked_softmax_call(x, lens, block_n, interpret)


def _masked_softmax_core_fwd(x, lens, block_n, interpret):
    y = _masked_softmax_call(x, lens, block_n, interpret)
    return y, y


def _masked_softmax_core_bwd(block_n, interpret, y, g):
    yf = y.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    dx = yf * (gf - jnp.sum(gf * yf, axis=-1, keepdims=True))
    return dx.astype(y.dtype), None


_masked_softmax_core.defvjp(_masked_softmax_core_fwd,
                            _masked_softmax_core_bwd)


def masked_softmax(x, xlen, block_n=None, interpret=None):
    """Sequence softmax over the time dim of x [B, T] with true lengths
    xlen [B]: positions >= xlen contribute nothing and get 0. One VMEM
    pass per row block; differentiable (custom_vjp from the saved
    output — masked positions have y == 0, so their grads vanish
    exactly like the unfused where-mask path)."""
    if interpret is None:
        interpret = _interpret_default()
    return _masked_softmax_core(x, jnp.asarray(xlen, jnp.int32),
                                _tile("seq", "block_n", block_n),
                                bool(interpret))


def _masked_pool_kernel(x_ref, len_ref, o_ref, *, ptype):
    x = x_ref[:].astype(jnp.float32)                         # [bn, T, F]
    lens = len_ref[:]                                        # [bn, 1]
    cols = lax.broadcasted_iota(jnp.int32, x.shape[:2], 1)
    m = (cols < lens).astype(jnp.float32)[:, :, None]        # [bn, T, 1]
    s = jnp.sum(x * m, axis=1)                               # [bn, F]
    denom = jnp.maximum(lens.astype(jnp.float32), 1.0)       # [bn, 1]
    if ptype == "AVERAGE":
        s = s / denom
    elif ptype == "SQRT":
        s = s / jnp.sqrt(denom)
    o_ref[:] = s.astype(o_ref.dtype)


@kernel_entry("ptpu_masked_pool",
              static_argnames=("ptype", "block_n", "interpret"))
def _masked_pool_rows(x, lens, *, ptype, block_n, interpret):
    """`_masked_pool_call` on whole blocks of block_n rows, lens [N, 1]
    int32."""
    n_pad, t, f = x.shape
    return pl.pallas_call(
        functools.partial(_masked_pool_kernel, ptype=ptype),
        grid=(n_pad // block_n,),
        in_specs=[
            _vmem_spec((block_n, t, f), lambda i: (i, 0, 0)),
            _vmem_spec((block_n, 1), lambda i: (i, 0)),
        ],
        out_specs=_vmem_spec((block_n, f), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, f), x.dtype),
        interpret=interpret,
        name="ptpu_masked_pool",
    )(x, lens)


def _masked_pool_call(x, lens, ptype, block_n, interpret):
    n = x.shape[0]
    n_pad = int(-(-n // block_n) * block_n)
    return _masked_pool_rows(
        _pad_rows(x, n_pad),
        _pad_rows(lens.reshape(-1, 1).astype(jnp.int32), n_pad),
        ptype=ptype, block_n=block_n, interpret=interpret)[:n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _masked_pool_core(x, lens, ptype, block_n, interpret):
    return _masked_pool_call(x, lens, ptype, block_n, interpret)


def _masked_pool_core_fwd(x, lens, ptype, block_n, interpret):
    out = _masked_pool_call(x, lens, ptype, block_n, interpret)
    # residuals must be jax values: a 0-size sentinel carries x's
    # shape[1:]/dtype (the layer_norm kernel's bias trick)
    return out, (lens, jnp.zeros((0,) + x.shape[1:], x.dtype))


def _masked_pool_core_bwd(ptype, block_n, interpret, res, g):
    lens, x_like = res
    t = x_like.shape[1]
    n = lens.shape[0]
    x_dtype = x_like.dtype
    m = (lax.broadcasted_iota(jnp.int32, (n, t), 1)
         < lens.reshape(-1, 1)).astype(jnp.float32)[:, :, None]
    gf = g.astype(jnp.float32)[:, None, :]                   # [N, 1, F]
    if ptype == "AVERAGE":
        gf = gf / jnp.maximum(lens.astype(jnp.float32), 1.0
                              ).reshape(-1, 1, 1)
    elif ptype == "SQRT":
        gf = gf / jnp.sqrt(jnp.maximum(lens.astype(jnp.float32), 1.0)
                           ).reshape(-1, 1, 1)
    return (gf * m).astype(x_dtype), None


_masked_pool_core.defvjp(_masked_pool_core_fwd, _masked_pool_core_bwd)


def masked_pool(x, xlen, ptype="AVERAGE", block_n=None, interpret=None):
    """Masked sequence pool over the time dim of x [B, T, F]:
    SUM / AVERAGE / SQRT (the linear pools — MAX/LAST/FIRST keep the
    dense path, their grads are selection-shaped). Returns [B, F];
    differentiable (custom_vjp, exact: the pools are linear in x)."""
    if ptype not in ("SUM", "AVERAGE", "SQRT"):
        raise ValueError("masked_pool handles SUM/AVERAGE/SQRT, got %r"
                         % (ptype,))
    if interpret is None:
        interpret = _interpret_default()
    return _masked_pool_core(x, jnp.asarray(xlen, jnp.int32), str(ptype),
                             _tile("seq", "block_n", block_n),
                             bool(interpret))


def layer_norm(x, scale, bias, eps=1e-5, block_n=None, interpret=None):
    """Fused layer norm over the trailing dim of 2D x [N, D]; one VMEM pass
    computes y + the (mean, rstd) backward residuals. Differentiable
    (custom_vjp; dense backward — the fwd is the HBM-bound pass worth
    fusing). `block_n` rows a grid step where given (a sweep, a kernel
    test); else as many as DEFAULT_TILES["ln"]'s byte budget holds at this
    N, D and dtype (_ln_block_rows). Returns (y, mean [N], variance [N])
    matching the layer_norm op's output contract; the fetchable
    mean/variance are plain reductions XLA DCEs when (as usual) nothing
    consumes them."""
    if interpret is None:
        interpret = _interpret_default()
    y = _ln_core(x, scale, bias, float(eps),
                 None if block_n is None else int(block_n), bool(interpret))
    xf = x.astype(jnp.float32)
    return y, jnp.mean(xf, axis=-1), jnp.var(xf, axis=-1)


# Who runs the routed experts' grouped matmuls beside XLA's `ragged-dot*`
# instructions (parallel/moe.py `_grouped_matmul` on one TPU): the kernels of
# ops/expert_gmm.py, its KERNELS written out so that nothing here imports
# them. The benchmark's expert_matmul_ms_per_step sums these names' calls.
# Kept at the end of the module: no line above a kernel moves.
EXPERT_MATMUL_KERNELS = ("ptpu_expert_gmm_fwd", "ptpu_expert_gmm_drows",
                         "ptpu_expert_gmm_dweights")
# Who runs a Mamba mixer's selective scan (ops/selective_scan_kernels.py, its
# two passes written out so that nothing here imports them): the benchmark's
# selective_scan_ms_per_step sums these names' calls.
SELECTIVE_SCAN_KERNELS = ("ptpu_selective_scan_fwd",
                          "ptpu_selective_scan_bwd")
# Who runs a Mamba-2 mixer's state-space-dual scan (ops/ssd_kernels.py, its
# two passes over chunks written out so that nothing here imports them): the
# benchmark's ssd_scan_ms_per_step sums these names' calls.
SSD_KERNELS = ("ptpu_ssd_fwd", "ptpu_ssd_bwd")
# PR 65, named here at the module's end and not in the tuples above, which
# would move every kernel's lines: the forward walk with the experts' unit as
# its epilogue does work the benchmark's expert_matmul_ms_per_step counts;
# the call whose output a buffer of sorted rows starts from (it writes
# nothing) is a Mosaic call of the program's and no expert matmul.
EXPERT_MATMUL_KERNELS += ("ptpu_expert_gmm_unit_fwd",)
KERNEL_NAMES += EXPERT_MATMUL_KERNELS[-1:] + ("ptpu_expert_rows_unwritten",)
# PR 70: ops/rotary_kernels.py's one pass over a rotary_embedding's heads,
# forward and (at the negated angle) backward.
KERNEL_NAMES += ("ptpu_rotary",)
# PR 71: ops/kda_kernels.py's pass over chunks and its reverse (the delta
# rule with a decay a key channel).
KDA_KERNELS = ("ptpu_kda_fwd", "ptpu_kda_bwd")
KERNEL_NAMES += KDA_KERNELS
# PR 72: ops/rms_norm_kernels.py's one pass, the transpose of an rms_norm
# over the heads of its rows.
KERNEL_NAMES += ("ptpu_rms_norm_bwd",)
