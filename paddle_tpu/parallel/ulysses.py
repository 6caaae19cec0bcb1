"""All-to-all (DeepSpeed-Ulysses-style) sequence/context parallelism.

The complement of ring attention (SURVEY.md §2 long-context: "ring
attention or all-to-all sequence/context parallelism"): instead of rotating
K/V blocks around the `sp` ring, ONE all_to_all over ICI re-shards the
activations from sequence-sharded [B, T/sp, H, D] to head-sharded
[B, T, H/sp, D]; every chip then runs plain dense attention over the FULL
sequence for its head group, and a final all_to_all restores the sequence
sharding. Four all_to_all ops total per attention (q/k/v in, output back)
in two communication phases (vs sp-1 ppermute hops for the ring) at the
cost of requiring heads % sp == 0 — the standard trade: Ulysses when heads
are plentiful, ring when sequence is extreme.

The reference (March 2018) has no attention parallelism; this is TPU-first
design, not parity.
"""
from jax import lax

from .ring_attention import attention_reference, sp_shard_call

__all__ = ["ulysses_attention", "ulysses_attention_sharded"]


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None,
                      kv_len=None):
    """Per-shard body (use inside shard_map): q/k/v are the local
    sequence shards [B, T/sp, H, D]; heads must divide by the axis size.
    kv_len: optional [B] true key lengths — after the all-to-all each
    shard holds the FULL sequence for its head slice, so key-padding is
    the plain dense mask."""
    sp = lax.axis_size(axis_name)
    h = q.shape[2]
    if h % sp != 0:
        raise ValueError(
            "ulysses_attention needs heads %% sp == 0 (got %d heads over "
            "sp=%d); use ring_attention for head-scarce long-context" %
            (h, sp))

    def seq_to_heads(x):
        # [B, T/sp, H, D] -> [B, T, H/sp, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def heads_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = attention_reference(qh, kh, vh, causal=causal, scale=scale,
                              kv_len=kv_len)
    return heads_to_seq(out)


def ulysses_attention_sharded(q, k, v, mesh, causal=False, scale=None,
                              batch_axis="dp", seq_axis="sp", kv_len=None):
    """Global-view entry: full (or GSPMD-sharded) [B, T, H, D] arrays;
    shard_map splits over (dp, sp) and runs the all-to-all attention.
    kv_len: optional [B] int32 global true key lengths (sharded over the
    batch axis like q's batch dim)."""
    def body(qs, ks, vs, lens):
        return ulysses_attention(qs, ks, vs, axis_name=seq_axis,
                                 causal=causal, scale=scale, kv_len=lens)

    return sp_shard_call(body, q, k, v, mesh, batch_axis, seq_axis, kv_len)
