"""The three flash kernels under the block-diffusion mask (ops/
pallas_kernels.py `block_diffusion=(block_length, L)`: a noised copy of L
tokens in rows 0 .. L - 1, a clean copy in rows L .. 2 L - 1), in interpret
mode: forward and the three gradients against a dense masked softmax and
jax.grad of it, with the mask written out HERE from the (copy, position,
block) rule; the pure range function `_bd_blocks` (every key block inside
its ranges has a visible pair and none outside has, forward and
transposed); what the entry refuses; the dense path's mask; and the calls
without the mask, whose traced kernels are the parent's to the letter."""
import hashlib
import itertools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.parallel.ring_attention import (attention_reference,
                                                block_diffusion_mask)


def _mask(copy_len, block_length):
    """[2 L, 2 L], row r sees row s, by (copy, position, block)."""
    seen = np.zeros((2 * copy_len, 2 * copy_len), bool)
    rows = [(copy, i, i // block_length) for copy in ("noised", "clean")
            for i in range(copy_len)]
    for r, (copy_r, _, b_r) in enumerate(rows):
        for s, (copy_s, _, b_s) in enumerate(rows):
            if copy_r == "noised" and copy_s == "noised":
                seen[r, s] = b_s == b_r
            elif copy_r == "noised" and copy_s == "clean":
                seen[r, s] = b_s < b_r
            elif copy_r == "clean" and copy_s == "clean":
                seen[r, s] = b_s <= b_r
    return seen


def _dense(q, k, v, seen):
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    s = jnp.where(jnp.asarray(seen), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _error(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


# (L, block_length, block_q, block_k, query heads, key/value heads, D): block
# lengths 1, 3, 4 and L; 8 query heads on 2; 2 L that is no multiple of a tile
# (40, 56); tiles smaller than (8 under 16), equal to (8 on 8) and larger
# than a block; two heads a lane block at D = 64
CASES = [
    (16, 4, 8, 8, 8, 2, 128), (16, 1, 8, 16, 8, 2, 128),
    (16, 16, 8, 8, 8, 2, 128), (20, 4, 16, 16, 8, 2, 128),
    (28, 4, 16, 8, 8, 2, 128), (16, 8, 8, 8, 4, 4, 64),
    (18, 3, 8, 16, 4, 2, 128),
    (32, 16, 8, 16, 2, 1, 128), (24, 4, 32, 16, 8, 2, 128)]


@pytest.mark.parametrize("case", CASES, ids=lambda c: "-".join(map(str, c)))
def test_kernels_match_the_dense_masked_softmax(case):
    copy, length, bq, bk, hq, hkv, d = case
    t = 2 * copy
    keys = jax.random.split(jax.random.key(copy * 131 + length), 4)
    q = jax.random.normal(keys[0], (2, t, hq, d))
    k = jax.random.normal(keys[1], (2, t, hkv, d))
    v = jax.random.normal(keys[2], (2, t, hkv, d))
    g = jax.random.normal(keys[3], (2, t, hq, d))
    seen = _mask(copy, length)
    with jax.default_matmul_precision("highest"):
        out, vjp = jax.vjp(lambda q, k, v: pk.flash_attention(
            q, k, v, block_q=bq, block_k=bk, interpret=True,
            block_diffusion=(length, copy)), q, k, v)
        want, want_vjp = jax.vjp(lambda q, k, v: _dense(q, k, v, seen),
                                 q, k, v)
        grads, want_grads = vjp(g), want_vjp(g)
    assert _error(out, want) < 2e-5
    for got, ref in zip(grads, want_grads):
        assert _error(got, ref) < 2e-5
    # the dense path of the op is the same function of the same mask
    assert (np.asarray(block_diffusion_mask(length, copy)) == seen).all()
    assert _error(attention_reference(q, k, v, block_diffusion=(
        length, copy)), want) < 2e-5


def test_bf16_operands_stay_within_half_a_percent():
    copy, length = 32, 4
    keys = jax.random.split(jax.random.key(7), 4)
    q, k, v, g = (jax.random.normal(
        key, (1, 2 * copy, h, 128)).astype(jnp.bfloat16)
        for key, h in zip(keys, (8, 2, 2, 8)))
    out, vjp = jax.vjp(lambda q, k, v: pk.flash_attention(
        q, k, v, block_q=16, block_k=16, interpret=True,
        block_diffusion=(length, copy)), q, k, v)
    want, want_vjp = jax.vjp(lambda q, k, v: _dense(
        q, k, v, _mask(copy, length)), *(x.astype(jnp.float32)
                                         for x in (q, k, v)))
    assert _error(out.astype(jnp.float32), want) < 1e-2
    for got, ref in zip(vjp(g), want_vjp(g.astype(jnp.float32))):
        assert _error(got.astype(jnp.float32), ref) < 2e-2


RANGES = [(copy, length, bi, bj)
          for copy, length, bi, bj in itertools.product(
              (8, 16, 24, 40), (1, 2, 3, 4, 8), (8, 16, 24), (8, 16, 32))
          if copy % length == 0]


@pytest.mark.parametrize("transposed", [False, True])
def test_every_block_inside_the_ranges_has_a_visible_pair(transposed):
    """And none outside has: no key block without a visible pair is
    streamed, forward, dQ (the same ranges) or dK/dV (transposed)."""
    for copy, length, bi, bj in RANGES:
        seen = _mask(copy, length)
        seen = seen.T if transposed else seen
        for i in range(-(-2 * copy // bi)):
            first, second = (tuple(int(x) for x in r) for r in pk._bd_blocks(
                i, bi, bj, (length, copy), transposed))
            assert first[0] <= first[1] <= second[0] <= second[1]
            inside = set(range(*first)) | set(range(*second))
            for j in range(-(-2 * copy // bj)):
                has = seen[i * bi:(i + 1) * bi, j * bj:(j + 1) * bj].any()
                assert has == (j in inside), (copy, length, bi, bj, i, j)


def test_block_visits_at_the_cells_tiles():
    """T = 4096 at 512 x 512: 80 key-block visits a head for 64.06 blocks'
    worth of visible pairs; a noised query block streams its own diagonal
    block and the clean blocks up to its frontier, a clean one the clean
    blocks alone."""
    visits = [pk._bd_blocks(i, 512, 512, (4, 4096)) for i in range(16)]
    counts = [sum(int(end) - int(first) for first, end in r) for r in visits]
    assert counts == [i + 2 for i in range(8)] + [i + 1 for i in range(8)]
    assert sum(counts) == 80
    for i in range(8):
        (a0, a1), (b0, b1) = (tuple(map(int, r)) for r in visits[i])
        assert (a0, a1, b0, b1) == (i, i + 1, 8, 9 + i)
        (a0, a1), (b0, b1) = (tuple(map(int, r)) for r in visits[8 + i])
        assert a0 == a1 and (b0, b1) == (8, 9 + i)
    back = [pk._bd_blocks(j, 512, 512, (4, 4096), True) for j in range(16)]
    assert sum(int(end) - int(first) for r in back for first, end in r) == 80


def test_the_entry_refuses_the_mask_beside_another():
    q = jnp.zeros((1, 16, 2, 8))
    for extra in (dict(causal=True), dict(window=4),
                  dict(kv_len=jnp.array([16])),
                  dict(q_rope=q, k_rope=q[:, :, :1])):
        with pytest.raises(ValueError, match="whole mask"):
            pk.flash_attention(q, q, q, block_diffusion=(4, 8), **extra)
    for bad in ((4, 16), (3, 8), (0, 8)):
        with pytest.raises(ValueError, match="T = 2 L"):
            pk.flash_attention(q, q, q, block_diffusion=bad)


# the traced forward and backward kernels of calls WITHOUT the mask, as a
# digest of their jaxpr's text: the PARENT's (commit 4aaa733, computed by
# `_digest` from a `git archive` of it), so `causal` / `window` / `kv_len`
# calls lower to the kernels they lowered to, equation for equation
UNMASKED = {"causal": "d9ec822e4f17653e", "window": "2c29879fbbcd388b",
            "full": "eb3af813007cb000", "kv_len": "024870d563fbe82e"}


def _digest(name):
    kw = {"causal": dict(causal=True),
          "window": dict(causal=True, window=20), "full": dict(causal=False),
          "kv_len": dict(causal=True, kv_len=jnp.array([40]))}[name]
    q = jnp.zeros((1, 48, 4, 128), jnp.bfloat16)
    k = jnp.zeros((1, 48, 2, 128), jnp.bfloat16)

    def both(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: pk.flash_attention(
            q, k, v, block_q=16, block_k=32, interpret=True, **kw), q, k, v)
        return (out,) + vjp(g)
    text = str(jax.make_jaxpr(both)(q, k, k, q))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(UNMASKED))
def test_calls_without_the_mask_trace_the_kernels_they_did(name):
    assert _digest(name) == UNMASKED[name]


@pytest.mark.parametrize("causal, window", [(True, None), (True, 12),
                                            (False, None)])
def test_calls_without_the_mask_give_what_they_gave(causal, window):
    keys = jax.random.split(jax.random.key(3), 3)
    q = jax.random.normal(keys[0], (1, 40, 4, 128))
    k = jax.random.normal(keys[1], (1, 40, 2, 128))
    v = jax.random.normal(keys[2], (1, 40, 2, 128))
    with jax.default_matmul_precision("highest"):
        got = pk.flash_attention(q, k, v, causal=causal, window=window,
                                 block_q=16, block_k=8, interpret=True)
        want = attention_reference(q, k, v, causal=causal, window=window)
    assert _error(got, want) < 2e-5


# --- the three kernels at the cell's shapes, compiled here without the chip -

@pytest.fixture(scope="module")
def one_chip():
    import os
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler on this machine
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def test_the_kernels_compile_for_a_described_v5e_at_the_cells_shapes(
        one_chip):
    """32 query heads on 4 of 128, two copies of 4096 tokens (8192 rows:
    the longest the pinned keys and values allow at D = 128), blocks of 4,
    the default 512 x 512 tiles, bf16: Mosaic takes all three kernels, each
    once, under its own name."""
    import re
    from jax.experimental.compilation_cache import compilation_cache

    def sds(heads):
        return jax.ShapeDtypeStruct((1, 8192, heads, 128), jnp.bfloat16,
                                    sharding=one_chip)

    def both(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: pk.flash_attention(
            q, k, v, interpret=False, block_diffusion=(4, 4096)), q, k, v)
        return (out,) + vjp(g)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        text = jax.jit(both).lower(sds(32), sds(4), sds(4),
                                   sds(32)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    calls = re.findall(
        r'%?([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"', text)
    assert sorted(name.partition(".")[0] for name in calls) == [
        "ptpu_flash_bwd_dkdv", "ptpu_flash_bwd_dq", "ptpu_flash_fwd"]
