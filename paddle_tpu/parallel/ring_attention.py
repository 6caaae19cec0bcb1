"""Ring attention: exact attention over sequences sharded across chips.

TPU-first long-context support (SURVEY.md §2 "long-context"). The reference
(mozga-intel/Paddle, March 2018) has no attention-parallelism at all — its
ring is the pserver update ring (python/paddle/v2/master, pserver/). Here the
ring is over the `sp` mesh axis: Q/K/V live sharded on the sequence dim, each
chip holds one block, and K/V blocks rotate around the ring via ppermute over
ICI while every chip accumulates its Q-block's attention with an online
(flash-style, numerically stable) softmax. Peak memory per chip is O(T/sp · T/sp)
instead of O(T·T), and no chip ever materializes the full sequence.

Layout convention: [batch, seq, heads, head_dim] ("BTHD"), sharded P(dp, sp)
on (batch, seq). Works under jit inside a Mesh context; differentiable
(jax.grad flows through shard_map + ppermute, giving the ring backward pass
with reverse-direction permutes automatically).
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map

from .mesh import P, vary as _vary

__all__ = ["ring_attention", "attention_reference", "ring_attention_sharded",
           "sequence_parallel_specs"]

_NEG_INF = -1e30


def block_diffusion_mask(block_length, copy_len):
    """[2 L, 2 L] bool of the block-diffusion mask (BD3-LM,
    arXiv:2503.09573): rows 0 .. L - 1 are the noised copy of a sequence of
    L tokens and rows L .. 2 L - 1 the clean one; row r is (copy, position
    i, block b = i // block_length) and sees row s iff both are noised and
    b_s = b_r, or r is noised, s clean and b_s < b_r, or both are clean and
    b_s <= b_r."""
    row = jnp.arange(2 * copy_len)
    noised = row < copy_len
    block = (row - jnp.where(noised, 0, copy_len)) // block_length
    qn, kn = noised[:, None], noised[None, :]
    qb, kb = block[:, None], block[None, :]
    return (qn & kn & (kb == qb)) | (qn & ~kn & (kb < qb)) \
        | (~qn & ~kn & (kb <= qb))


def attention_reference(q, k, v, causal=False, scale=None, kv_len=None,
                        window=None, block_diffusion=None):
    """Dense single-device attention, q [B,T,Hq,D], k and v [B,T,Hkv,D]. The
    numerical reference the ring path and the flash kernels must match; also
    the fallback when no `sp` axis exists and the path under the flash
    crossover. kv_len: optional [B] true key lengths (key-padding mask).
    Grouped queries from the shapes: query head h reads key/value head
    h // (Hq // Hkv). window: None or an int, query i sees key j only where
    i - j < window (with `causal`, the `window` newest keys up to itself).
    block_diffusion: None or (block_length, L): `block_diffusion_mask` over
    T = 2 L rows."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    group = q.shape[2] // k.shape[2]
    if group * k.shape[2] != q.shape[2]:
        raise ValueError("attention_reference: %d query heads are no "
                         "multiple of %d key/value heads"
                         % (q.shape[2], k.shape[2]))
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    tq, tk = logits.shape[-2], logits.shape[-1]
    if causal:
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool))
        logits = jnp.where(mask, logits, _NEG_INF)
    if window is not None:
        age = jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :]
        logits = jnp.where(age < window, logits, _NEG_INF)
    if block_diffusion is not None:
        logits = jnp.where(block_diffusion_mask(*block_diffusion), logits,
                           _NEG_INF)
    if kv_len is not None:
        # accept [B] or the fluid-convention [B, 1] (the flash kernel
        # normalizes the same way; a [B, 1] here would silently
        # broadcast the mask to rank 5)
        kv_len = jnp.asarray(kv_len).reshape(k.shape[0])
        kpos = jnp.arange(k.shape[1])
        kmask = kpos[None, :] < kv_len[:, None]           # [B, Tk]
        logits = jnp.where(kmask[:, None, None, :], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block_attend(q, k, v, m, l, o, q_off, k_off, causal, scale,
                  kv_len=None):
    """One online-softmax accumulation step against a single K/V block.

    q: [B,Tq,H,D]  k,v: [B,Tk,H,D]  m,l: [B,H,Tq]  o: [B,Tq,H,D]
    q_off/k_off: global position offsets of the blocks (for causal mask
    and the kv_len key-padding mask; kv_len is [B] true key lengths).
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale  # [B,H,Tq,Tk]
    kpos = k_off + jnp.arange(k.shape[1])
    if causal:
        qpos = q_off + jnp.arange(q.shape[1])
        mask = qpos[:, None] >= kpos[None, :]
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    if kv_len is not None:
        kmask = kpos[None, :] < kv_len[:, None]           # [B, Tk]
        logits = jnp.where(kmask[:, None, None, :], logits, _NEG_INF)
    m_blk = jnp.max(logits, axis=-1)                      # [B,H,Tq]
    m_new = jnp.maximum(m, m_blk)
    p = jnp.exp(logits - m_new[..., None])                # [B,H,Tq,Tk]
    if causal or kv_len is not None:
        # fully-masked rows would give exp(NEG_INF - NEG_INF) = 1 everywhere;
        # force masked entries to exact zero so l stays 0 and the final
        # clamp yields a zero output row
        p = jnp.where(logits <= _NEG_INF * 0.5, 0.0, p)
    corr = jnp.exp(m - m_new)                             # [B,H,Tq]
    l_new = l * corr + jnp.sum(p, axis=-1)
    # o is [B,Tq,H,D]; corr broadcasts as [B,Tq,H,1]
    corr_o = jnp.transpose(corr, (0, 2, 1))[..., None]
    o_new = o * corr_o + jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return m_new, l_new, o_new


def _ring_body(axis_name, n, causal, scale, t_q, t_k, kv_len=None):
    def body(step, carry):
        k, v, m, l, o, q, my_idx = carry
        # block currently held arrived from device (my_idx - step) mod n
        src = jnp.mod(my_idx - step, n)
        m, l, o = _block_attend(q, k, v, m, l, o,
                                q_off=my_idx * t_q, k_off=src * t_k,
                                causal=causal, scale=scale, kv_len=kv_len)
        # rotate K/V one hop around the ring (skip after the last block so
        # the loop does exactly n-1 permutes)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k, v = lax.cond(
            step < n - 1,
            lambda kv: tuple(lax.ppermute(x, axis_name, perm) for x in kv),
            lambda kv: kv, (k, v))
        return (k, v, m, l, o, q, my_idx)
    return body


def ring_attention(q, k, v, axis_name="sp", causal=False, scale=None,
                   vary_axes=None, kv_len=None):
    """Per-shard ring attention; call inside shard_map over `axis_name`.

    q,k,v: the LOCAL sequence blocks [B, T/sp, H, D]. kv_len: optional
    [B] int32 GLOBAL true key lengths (padded-batch masking — keys at
    global position >= kv_len contribute nothing; same contract as
    pallas flash_attention's kv_len). Returns local output block
    [B, T/sp, H, D]. Exact (not approximate): matches
    attention_reference on the gathered result to fp32 tolerance.
    """
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    b, t_q, h, d = q.shape
    t_k = k.shape[1]
    # accumulators start as constants; mark them device-varying over the ring
    # axis so the fori_loop carry type is stable under shard_map
    axes = tuple(vary_axes or (axis_name,))
    m0 = _vary(jnp.full((b, h, t_q), _NEG_INF, dtype=jnp.float32), axes)
    l0 = _vary(jnp.zeros((b, h, t_q), dtype=jnp.float32), axes)
    o0 = _vary(jnp.zeros(q.shape, dtype=jnp.float32), axes)
    if kv_len is not None:
        kv_len = jnp.asarray(kv_len, jnp.int32).reshape(b)
    body = _ring_body(axis_name, n, causal, scale, t_q, t_k, kv_len=kv_len)
    _, _, m, l, o, _, _ = lax.fori_loop(
        0, n, body, (k, v, m0, l0, o0, q.astype(jnp.float32), my_idx))
    l = jnp.maximum(l, 1e-30)  # fully-masked rows (strict causal pad) → 0 out
    out = o / jnp.transpose(l, (0, 2, 1))[..., None]
    return out.astype(q.dtype)


def sequence_parallel_specs(batch_axis="dp", seq_axis="sp"):
    """PartitionSpecs for BTHD activations under sequence parallelism."""
    return P(batch_axis, seq_axis, None, None)


def sp_spec_for_mesh(mesh, batch_axis, seq_axis):
    """The [B,T,H,D] PartitionSpec for an SP entry point on `mesh`: batch
    over batch_axis when the mesh has one, sequence over seq_axis. Shared
    by ring_attention_sharded and ulysses_attention_sharded."""
    if batch_axis in mesh.axis_names:
        return sequence_parallel_specs(batch_axis, seq_axis), \
            (batch_axis, seq_axis)
    return P(None, seq_axis, None, None), (seq_axis,)


def sp_shard_call(body, q, k, v, mesh, batch_axis, seq_axis, kv_len):
    """Shared SP entry plumbing for ring and ulysses: shard q/k/v over
    (batch_axis, seq_axis), kv_len (if any) over the batch axis, and run
    `body(qs, ks, vs, lens)` per shard. The single place that owns the
    kv_len sharding contract ([B] int32, batch-sharded)."""
    spec, _ = sp_spec_for_mesh(mesh, batch_axis, seq_axis)
    if kv_len is None:
        fn = shard_map(lambda qs, ks, vs: body(qs, ks, vs, None),
                       mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec)
        return fn(q, k, v)
    len_spec = P(batch_axis) if batch_axis in mesh.axis_names else P()
    fn = shard_map(body, mesh=mesh,
                   in_specs=(spec, spec, spec, len_spec), out_specs=spec)
    return fn(q, k, v, jnp.asarray(kv_len, jnp.int32).reshape(q.shape[0]))


def ring_attention_sharded(q, k, v, mesh, causal=False, scale=None,
                           batch_axis="dp", seq_axis="sp", kv_len=None):
    """Global-view ring attention: q,k,v are full [B,T,H,D] arrays (or GSPMD
    -sharded); shard_map splits them over (dp, sp) and runs the ring.
    kv_len: optional [B] int32 global true key lengths (sharded over the
    batch axis like q's batch dim).
    """
    _, vary_axes = sp_spec_for_mesh(mesh, batch_axis, seq_axis)

    def body(qs, ks, vs, lens):
        return ring_attention(qs, ks, vs, axis_name=seq_axis, causal=causal,
                              scale=scale, vary_axes=vary_axes, kv_len=lens)

    return sp_shard_call(body, q, k, v, mesh, batch_axis, seq_axis, kv_len)
