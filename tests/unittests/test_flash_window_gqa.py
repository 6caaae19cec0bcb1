"""The three flash kernels with a sliding window and grouped queries, in the
interpreter, against the dense path (parallel/ring_attention.py
attention_reference), which takes both as well: forward, dQ, dK and dV for
window x causal x grouped queries x a T that is no multiple of the block,
with windows smaller than, equal to and larger than T."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_kernels import flash_attention
from paddle_tpu.parallel.ring_attention import attention_reference

D = 16
BLOCK_Q, BLOCK_K = 16, 8


def _inputs(t, hq, hkv, seed=0, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    q, g = (jnp.asarray(rng.randn(2, t, hq, D), dtype) for _ in range(2))
    k, v = (jnp.asarray(rng.randn(2, t, hkv, D), dtype) for _ in range(2))
    return q, k, v, g


def _both(q, k, v, g, **kw):
    """(flash, dense): each (out, dq, dk, dv) for the cotangent g."""
    def run(fn, **extra):
        out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, **kw, **extra),
                           q, k, v)
        return (out,) + vjp(g.astype(out.dtype))
    return (run(flash_attention, block_q=BLOCK_Q, block_k=BLOCK_K,
                interpret=True), run(attention_reference))


def _error(got, want):
    """Largest error over the largest value (the inputs are O(1); a window
    of 1 has dq = dk = 0, so the divisor has a floor)."""
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / max(np.abs(want).max(), 1.0))


@pytest.mark.parametrize("t", [40, 64])          # 40: no multiple of 16
@pytest.mark.parametrize("heads", [(4, 4), (4, 2), (6, 1)])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("window", [None, 12, "T", 100])
def test_kernels_against_the_dense_path(window, causal, heads, t):
    window = t if window == "T" else window
    q, k, v, g = _inputs(t, *heads)
    flash, dense = _both(q, k, v, g, causal=causal, window=window)
    assert flash[1].shape == q.shape and flash[2].shape == k.shape \
        and flash[3].shape == v.shape
    for name, got, want in zip(("out", "dq", "dk", "dv"), flash, dense):
        assert _error(got, want) < 2e-5, (name, _error(got, want))


@pytest.mark.parametrize("window", [1, 7, 16, 17])
def test_window_edges_fall_inside_and_on_block_borders(window):
    """A window of 1 sees the query's own key only; 16 and 17 put the
    window's edge on and one past a block border."""
    q, k, v, g = _inputs(48, 2, 1, seed=3)
    flash, dense = _both(q, k, v, g, causal=True, window=window)
    for got, want in zip(flash, dense):
        assert _error(got, want) < 2e-5
    if window == 1:
        np.testing.assert_allclose(flash[0], jnp.repeat(v, 2, axis=2),
                                   rtol=1e-6)


def test_window_with_key_lengths():
    """kv_len and a window together; the lengths leave every query a key
    (a query all of whose window lies past kv_len has no row to compare:
    the kernel gives zeros, the dense softmax an average)."""
    q, k, v, g = _inputs(40, 4, 2, seed=5)
    kv_len = jnp.asarray([36, 40])
    flash, dense = _both(q, k, v, g, causal=True, window=9, kv_len=kv_len)
    for got, want in zip(flash, dense):
        assert _error(got, want) < 2e-5


def test_a_window_of_t_or_more_is_no_window():
    q, k, v, g = _inputs(40, 4, 2, seed=7)
    plain = flash_attention(q, k, v, causal=True, block_q=BLOCK_Q,
                            block_k=BLOCK_K, interpret=True)
    for window in (40, 41, 4096):
        np.testing.assert_array_equal(plain, flash_attention(
            q, k, v, causal=True, window=window, block_q=BLOCK_Q,
            block_k=BLOCK_K, interpret=True))


def test_bf16_grouped_window_stays_within_bf16_of_float32():
    q, k, v, g = _inputs(64, 4, 1, seed=9, dtype=jnp.bfloat16)
    flash, _ = _both(q, k, v, g, causal=True, window=20)
    _, dense = _both(*(x.astype(jnp.float32) for x in (q, k, v, g)),
                     causal=True, window=20)
    assert flash[2].dtype == jnp.bfloat16 and flash[2].shape == k.shape
    for got, want in zip(flash, dense):
        assert _error(got, want) < 1e-2


def test_out_of_band_blocks_are_skipped():
    """The loop bounds, as the kernels compute them: a q block streams only
    the k blocks between the window's edge and the causal frontier."""
    from paddle_tpu.ops.pallas_kernels import _k_blocks
    # 8 blocks of 512, window 1024 (two blocks): q block 5 holds queries
    # 2560..3071; its first query sees keys 1537.., so k blocks 3, 4, 5
    first, end = _k_blocks(jnp.int32(5), jnp.int32(4096), True, 1024, 512,
                           512, 4096)
    assert (int(first), int(end)) == (3, 6)
    first, end = _k_blocks(jnp.int32(0), jnp.int32(4096), True, 1024, 512,
                           512, 4096)
    assert (int(first), int(end)) == (0, 1)
    first, end = _k_blocks(jnp.int32(5), jnp.int32(4096), True, None, 512,
                           512, 4096)
    assert (first, int(end)) == (0, 6)


def test_shapes_that_are_no_grouping_are_refused():
    q, k, v, _ = _inputs(16, 4, 3)
    with pytest.raises(ValueError, match="Hkv dividing"):
        flash_attention(q, k, v, interpret=True)
    with pytest.raises(ValueError, match="multiple"):
        attention_reference(q, k, v)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, window=0, interpret=True)
