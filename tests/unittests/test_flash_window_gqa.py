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


# --- heads indexed in place: [B, T, H*D], so many heads a lane block ---------

# id: (T, Hq, Hkv, D, kwargs, heads a block); blocks of 16 x 16, so 40 is no
# multiple of the block
_IN_PLACE = {
    "d64_two_a_block_causal": (32, 8, 8, 64, dict(causal=True), 2),
    "d64_two_a_block_full": (32, 8, 8, 64, dict(), 2),
    "d64_two_a_block_ragged_keys": (40, 8, 8, 64, dict(kv_len=[40, 23]), 2),
    "d128_ungrouped": (32, 2, 2, 128, dict(causal=True), 1),
    "d128_7_on_1_window": (48, 7, 1, 128, dict(causal=True, window=20), 1),
    "d256_16_on_2": (32, 16, 2, 256, dict(causal=True), 1),
    "d128_t_no_multiple": (40, 3, 3, 128, dict(causal=True), 1),
    "d32_four_a_block": (40, 8, 8, 32, dict(causal=True), 4),
    "tiny_heads_one_block": (40, 3, 3, 16, dict(causal=True), 3),
    "d64_grouped_transposed": (32, 4, 2, 64, dict(causal=True), None),
    "d64_three_heads_transposed": (32, 3, 3, 64, dict(causal=True), None),
}


@pytest.mark.parametrize("case", sorted(_IN_PLACE))
def test_heads_indexed_in_place_against_the_dense_path(case):
    """Forward, dQ, dK and dV of every way the kernels find a head: a lane
    block a head (D a multiple of 128, grouped or not), two and four heads
    a block (D=64, D=32), all the heads in one narrow block, and the two
    kinds of shape that still go through a transpose."""
    from paddle_tpu.ops.pallas_kernels import heads_a_block
    t, hq, hkv, d, kw, want = _IN_PLACE[case]
    assert heads_a_block(hq, hkv, d) == want
    rng = np.random.RandomState(len(case))
    q, g = (jnp.asarray(rng.randn(2, t, hq, d), jnp.float32)
            for _ in range(2))
    k, v = (jnp.asarray(rng.randn(2, t, hkv, d), jnp.float32)
            for _ in range(2))
    if "kv_len" in kw:
        kw = dict(kw, kv_len=jnp.asarray(kw["kv_len"]))
    flash, dense = _both(q, k, v, g, **kw)
    assert [x.shape for x in flash] == [q.shape, q.shape, k.shape, v.shape]
    for name, got, want in zip(("out", "dq", "dk", "dv"), flash, dense):
        assert _error(got, want) < 2e-5, (name, _error(got, want))


@pytest.mark.parametrize("causal", [True, False])
def test_two_heads_a_block_equal_a_head_alone_bit_for_bit(causal):
    """At D=64 a head's scores are (q2 * m_h) @ k2.T over the 128 lanes of
    its pair: the other head's lanes add exact zeros, so the output and the
    gradients are those of the head run alone (one head of 64 lanes is a
    block of its own: the old layout's row a head), bit for bit in
    float32."""
    q, k, v, g = (jnp.asarray(np.random.RandomState(i).randn(2, 40, 4, 64),
                              jnp.float32) for i in range(4))
    pair, _ = _both(q, k, v, g, causal=causal)
    for h in range(4):
        alone = _both(*(x[:, :, h:h + 1] for x in (q, k, v, g)),
                      causal=causal)[0]
        for got, want in zip(pair, alone):
            np.testing.assert_array_equal(got[:, :, h:h + 1], want)


# the six transformer-family cells' flash shapes: (q, k and v, kwargs)
_CELL_SHAPES = {
    "transformer_t2048_causal": ((8, 2048, 8, 64), 8, dict(causal=True)),
    "transformer_t2048_keys": ((8, 2048, 8, 64), 8, dict(kv_len=True)),
    "olmoe_t4096": ((4, 4096, 16, 128), 16, dict(causal=True)),
    "ouro_t4096": ((1, 4096, 16, 128), 16, dict(causal=True)),
    "smallthinker_t8192_window": ((1, 8192, 7, 128), 1,
                                  dict(causal=True, window=4096)),
    "qwen3_next_t4096": ((1, 4096, 16, 256), 2, dict(causal=True)),
}


@pytest.mark.parametrize("cell", sorted(_CELL_SHAPES))
def test_no_layout_pass_around_the_kernels_at_the_cells_shapes(cell):
    """flash_attention and its vjp, traced at a cell's shape: outside the
    three pallas_calls there is no transpose, and no operand or result of a
    pallas_call is a [.., T, 1] column (one lane in 128 in HBM)."""
    shape, hkv, kw = _CELL_SHAPES[cell]
    b, t, h, d = shape
    if kw.pop("kv_len", False):
        kw["kv_len"] = jnp.full((b,), t - 7, jnp.int32)
    q = jnp.zeros(shape, jnp.bfloat16)
    k = jnp.zeros((b, t, hkv, d), jnp.bfloat16)

    def both(q, k, v, g):
        out, vjp = jax.vjp(lambda q, k, v: flash_attention(
            q, k, v, interpret=False, **kw), q, k, v)
        return (out,) + vjp(g)
    calls, outside = [], []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                calls.append(e)
                continue            # a kernel's body is Mosaic's, not XLA's
            outside.append(e.primitive.name)
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub)
    walk(jax.make_jaxpr(both)(q, k, k, q).jaxpr)
    assert sorted(e.params["name"] for e in calls) == [
        "ptpu_flash_bwd_dkdv", "ptpu_flash_bwd_dq", "ptpu_flash_fwd"]
    assert "transpose" not in outside
    for e in calls:
        for var in list(e.invars) + list(e.outvars):
            assert not (var.aval.shape[-1] == 1 and var.aval.shape[-2] >= t), (
                e.params["name"], var.aval.shape)


# --- the forward kernel's k loop: out AND lse ------------------------------

def _dense_out_and_lse(q, k, v, scale, causal=False, window=None,
                       kv_len=None, q_rope=None, k_rope=None):
    """Float32 dense attention and its logsumexp [B, H, T] with the
    kernels' convention for a query that sees no key: out 0 and the lse a
    running max still at -1e30 gives."""
    q, k, v = (np.asarray(x, np.float32) for x in (q, k, v))
    b, t, hq, _ = q.shape
    k, v = (np.repeat(x, hq // x.shape[2], axis=2) for x in (k, v))
    s = np.einsum("bqhd,bkhd->bhqk", q, k)
    if q_rope is not None:
        s = s + np.einsum("bqhd,bkd->bhqk", np.asarray(q_rope, np.float32),
                          np.asarray(k_rope, np.float32)[:, :, 0])
    s = s * np.float32(scale)
    i, j = np.arange(t)[:, None], np.arange(t)[None, :]
    seen = np.ones((b, 1, t, t), bool)
    if causal:
        seen = seen & (i >= j)
    if window is not None:
        seen = seen & (i - j < window)
    if kv_len is not None:
        seen = seen & (j[None, None] < np.asarray(kv_len)[:, None, None,
                                                          None])
    s = np.where(seen, s, -np.inf)
    some = seen.any(-1)                                      # [B, 1, T]
    m = np.where(some, s.max(-1, initial=-np.inf), 0.0)
    p = np.exp(s - m[..., None])
    l = p.sum(-1)
    out = np.einsum("bhqk,bkhd->bqhd", p / np.maximum(l, 1e-30)[..., None],
                    v)
    lse = np.where(some, m + np.log(np.maximum(l, 1e-30)), np.float32(-1e30))
    return out, np.broadcast_to(lse, (b, hq, t))


# id: (T, Hq, Hkv, D, block_q, block_k, kwargs, dtype); `rope`: the latent
# form's rotary width
_FORWARD = {
    # prologue and last step are the whole loop: one k block a q block
    "one_block_full": (16, 2, 2, 16, 16, 16, dict(), "float32"),
    "one_block_a_q_block_causal": (64, 2, 2, 16, 16, 64,
                                   dict(causal=True), "float32"),
    "one_block_under_a_window": (64, 2, 2, 16, 16, 16,
                                 dict(causal=True, window=1), "float32"),
    # rows 20.. of q block 1 see no key of its first k block (keys 8..15)
    "window_first_block_hides_every_key": (
        48, 2, 2, 16, 16, 8, dict(causal=True, window=4), "float32"),
    "window_without_causal": (48, 2, 1, 16, 16, 8, dict(window=5),
                              "float32"),
    "no_keys_at_all": (32, 2, 2, 16, 16, 8, dict(kv_len=[0, 32]),
                       "float32"),
    "no_keys_causal": (32, 2, 2, 16, 16, 8,
                       dict(causal=True, kv_len=[0, 5]), "float32"),
    # a window that ends before the padded rows start: they see nothing
    "padded_rows_past_the_window": (48, 2, 2, 16, 16, 8,
                                    dict(window=6, kv_len=[9, 30]),
                                    "float32"),
    "t_no_multiple_of_the_blocks": (40, 3, 3, 16, 16, 8,
                                    dict(causal=True), "float32"),
    "t_no_multiple_mismatched_blocks": (50, 2, 2, 16, 16, 24,
                                        dict(kv_len=[50, 17]), "float32"),
    "two_heads_a_block": (40, 4, 4, 64, 16, 16,
                          dict(causal=True, kv_len=[40, 21]), "float32"),
    "grouped_in_place": (48, 7, 1, 128, 16, 16,
                         dict(causal=True, window=20), "float32"),
    "grouped_transposed": (40, 4, 2, 64, 16, 8, dict(causal=True),
                           "float32"),
    "lane_partials_bf16": (256, 2, 2, 64, 128, 256, dict(causal=True),
                           "bfloat16"),
    "lane_partials_ragged_keys": (256, 1, 1, 128, 128, 128,
                                  dict(kv_len=[256, 130, 0]), "float32"),
    "latent": (96, 4, 4, 128, 32, 32, dict(causal=True, rope=64),
               "float32"),
    "latent_no_keys": (64, 2, 2, 128, 32, 32,
                       dict(causal=True, rope=64, kv_len=[0, 40]),
                       "float32"),
}


@pytest.mark.parametrize("case", sorted(_FORWARD))
def test_forward_out_and_lse_against_float32_dense_attention(case):
    """`out` and `lse` of the forward kernel alone, where its k loop could
    break: a loop of one step, a first block that hides every key from some
    rows (their running max is still at its floor when the next block
    comes), rows with no key at all (out 0, lse -1e30 as always), padded T,
    two heads a lane block, grouped heads both ways, blocks of whole lane
    tiles (the row sums' lane partials) and the latent form."""
    from paddle_tpu.ops import pallas_kernels as pk
    t, hq, hkv, d, bq, bk, kw, dtype = _FORWARD[case]
    kw = dict(kw)
    dr = kw.pop("rope", None)
    b = len(kw["kv_len"]) if "kv_len" in kw else 2
    rng = np.random.RandomState(len(case))
    q = jnp.asarray(rng.randn(b, t, hq, d), dtype)
    k, v = (jnp.asarray(rng.randn(b, t, hkv, d), dtype) for _ in range(2))
    rope = None
    if dr:
        rope = (jnp.asarray(rng.randn(b, t, hq, dr), dtype),
                jnp.asarray(rng.randn(b, t, 1, dr), dtype))
    scale = 1.0 / float(np.sqrt(d + (dr or 0)))
    kv_len = jnp.asarray(kw.pop("kv_len", [t] * b), jnp.int32)
    causal, window = kw.get("causal", False), kw.get("window")
    out, res = pk._flash_core_fwd(q, k, v, rope, kv_len, scale, causal,
                                  window, bq, bk, True)
    lse = res[-1]
    assert out.shape == q.shape and out.dtype == q.dtype
    t_pad = pk._pad_t(t, bq, bk)
    assert lse.shape == (b * hq, t_pad // bq, 1, bq) \
        and lse.dtype == jnp.float32
    want_out, want_lse = _dense_out_and_lse(
        q, k, v, scale, causal, window, kv_len,
        *(rope or ()))
    tol = 2e-5 if dtype == "float32" else 1e-2
    assert _error(out, want_out) < tol, _error(out, want_out)
    got_lse = np.asarray(lse).reshape(b, hq, t_pad)[:, :, :t]
    some = want_lse > -1e29
    np.testing.assert_allclose(got_lse[some], want_lse[some], rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_array_equal(got_lse[~some], np.float32(-1e30))
    empty = np.broadcast_to(~some.transpose(0, 2, 1)[..., None], out.shape)
    np.testing.assert_array_equal(np.asarray(out, np.float32)[empty], 0.0)


@pytest.mark.parametrize("form", ["plain", "latent"])
def test_the_forward_loop_reduces_across_lanes_once_and_selects_once(form):
    """The k loop of `ptpu_flash_fwd`, as jax traces it: one reduction along
    the keys of a [bq, bk] tile (the row max; the row sums stay lane
    partials until the loop is over) and one select on the tile (the mask
    on the scores; the `exp` of a masked score is 0 by itself), and the
    loop carries the running max alone."""
    from paddle_tpu.ops import pallas_kernels as pk
    bq = bk = 256
    q = jnp.zeros((1, 512, 2, 128), jnp.bfloat16)
    kw = {}
    if form == "latent":
        kw = dict(q_rope=jnp.zeros((1, 512, 2, 64), jnp.bfloat16),
                  k_rope=jnp.zeros((1, 512, 1, 64), jnp.bfloat16))
    jaxpr = jax.make_jaxpr(lambda q: pk.flash_attention(
        q, q, q, causal=True, block_q=bq, block_k=bk, interpret=True,
        **kw))(q)
    loops, found = [], {}

    def walk(j, inside, loop):
        for e in j.eqns:
            name = e.primitive.name
            if loop and name in ("reduce_max", "reduce_sum", "select_n") \
                    and e.invars[-1].aval.shape == (bq, bk):
                found[name] = found.get(name, 0) + 1
            if name == "pallas_call":
                inside = e.params["name"] == "ptpu_flash_fwd"
            if inside and name == "while":
                loops.append(e)
            for sub in jax.core.jaxprs_in_params(e.params):
                walk(sub, inside, loop or (inside and name == "while"))
    walk(jaxpr.jaxpr, False, False)
    assert len(loops) == 1
    assert found == {"reduce_max": 1, "select_n": 1}, found
    carried = [v.aval.shape for v in loops[0].params["body_jaxpr"].jaxpr
               .outvars if getattr(v.aval, "shape", ()) != ()]
    assert carried == [(bq, 1)], carried
