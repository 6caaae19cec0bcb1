"""Runs the SDAR-30B-A3B-Chat cell with its model broken on purpose, to show
that `correct` can fail for what the cell measures.

    python benchmark/tests/mutant_sdar.py <mutant> <the arguments of benchmark/run.py>

Each mutant changes, in this process alone, one function the Program is
built or lowered through, and leaves the parameters, their shapes and their
order as they are, so the reference still reads the program's weights; then
the cell runs as benchmark/run.py runs it. Every mutant's last line has to
say `"correct": false`; the configuration's .json has what the chip gave.

A mutant of the MASK replaces it on both paths of the core: in the flash
kernels (the cell's T) the tile's mask is computed from the rows' numbers
by the mutant's rule and every key block is streamed (the ranges of
`_bd_blocks` are the right mask's), on the dense path (the rehearsal's)
`block_diffusion_mask` is the mutant's.

The last two are no mutants of the program: `reference_bf16_weights` and
`reference_fp8_weights` run the healthy program against the reference with
its weights rounded to bfloat16 (which has to stay correct: it is the
precision the configuration states) and to float8 e4m3, scaled a tensor,
the nearest precision below (which has to fail a tolerance).
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def _rule_with(op_type, change):
    """The registered lowering of `op_type` behind `change(ins, attrs) ->
    (ins, attrs)`."""
    from paddle_tpu.core import registry
    rule = registry.get(op_type)
    lower = rule.lower

    def changed(ctx, ins, attrs):
        return lower(ctx, *change(dict(ins), dict(attrs)))
    rule.lower = changed


def _mask_with(rule):
    """Both cores under `rule(q, k) -> visible`, q and k dicts of a row's
    `noised` flag, `position` in its copy and `block`, broadcast against
    each other (int32 / bool arrays)."""
    import importlib
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels as pk
    ring = importlib.import_module("paddle_tpu.parallel.ring_attention")

    def rows(pos, bd):
        length, copy = bd
        noised = pos < copy
        position = pos - jnp.where(noised, 0, copy)
        return {"noised": noised, "position": position,
                "block": position // length}

    def every_block(i, block_i, block_j, bd, transposed=False):
        return (0, -(-2 * bd[1] // block_j)), (0, 0)

    pk._bd_blocks = every_block
    pk._bd_query_codes = lambda qpos, bd: (rows(qpos, bd), bd)
    pk._bd_key_code = lambda kpos, bd: kpos
    pk._bd_visible = lambda valid, qcodes, kpos: valid & rule(
        qcodes[0], rows(kpos, qcodes[1]))

    def dense(block_length, copy_len):
        pos = jnp.arange(2 * copy_len)
        bd = (block_length, copy_len)
        return rule(rows(pos[:, None], bd), rows(pos[None, :], bd))
    ring.block_diffusion_mask = dense


def _right(q, k):
    """The mask as it is, by its three terms."""
    return (q["noised"] & k["noised"] & (k["block"] == q["block"])) \
        | (q["noised"] & ~k["noised"] & (k["block"] < q["block"])) \
        | (~q["noised"] & ~k["noised"] & (k["block"] <= q["block"]))


def mask_row_causal(fluid, causal_lm, moe):
    """Plain causal over the 2 T rows: a noised row sees the noised rows
    before it, a clean row every noised row and the clean rows before it."""
    def row(r):
        return r["position"] + (~r["noised"]) * (1 << 20)
    _mask_with(lambda q, k: row(q) >= row(k))


def clean_sees_noised(fluid, causal_lm, moe):
    """A clean row also sees the noised copy of the blocks up to its own."""
    _mask_with(lambda q, k: _right(q, k) | (
        ~q["noised"] & k["noised"] & (k["block"] <= q["block"])))


def noised_sees_own_clean_block(fluid, causal_lm, moe):
    """A noised row sees the clean copy of its OWN block too (b_s <= b_r):
    the answer leaks."""
    _mask_with(lambda q, k: _right(q, k) | (
        q["noised"] & ~k["noised"] & (k["block"] == q["block"])))


def noised_blind_to_clean(fluid, causal_lm, moe):
    """A noised row sees its own block alone: no clean context."""
    _mask_with(lambda q, k: _right(q, k) & ~(q["noised"] & ~k["noised"]))


def block_diagonal_one_way(fluid, causal_lm, moe):
    """Causal inside a noised block: position i sees its block's noised
    rows up to itself."""
    _mask_with(lambda q, k: _right(q, k) & ~(
        q["noised"] & k["noised"] & (k["position"] > q["position"])))


def clean_row_causal(fluid, causal_lm, moe):
    """Token-causal on the clean copy: a clean row does not see the rest of
    its own block."""
    _mask_with(lambda q, k: _right(q, k) & ~(
        ~q["noised"] & ~k["noised"] & (k["position"] > q["position"])))


def block_length_8(fluid, causal_lm, moe):
    """The mask of blocks of 8 on a batch noised by blocks of 4."""
    attend = fluid.layers.fused_attention

    def wider(q, k, v, block_diffusion=None, **kw):
        length, copy = block_diffusion
        return attend(q, k, v, block_diffusion=(2 * length, copy), **kw)
    fluid.layers.fused_attention = wider


def positions_run_on(fluid, causal_lm, moe):
    """The clean copy's rows carry positions T .. 2 T - 1, as rows of one
    long sequence would."""
    layers, attention = fluid.layers, causal_lm.attention

    def run_on(x, pos, c):
        noised, clean = layers.split(pos, 2, dim=1)
        clean = layers.cast(layers.scale(
            layers.cast(clean, "float32"), scale=1.0,
            bias=float(c["copies"][1])), "int64")
        return attention(x, layers.concat([noised, clean], axis=1), c)
    causal_lm.attention = run_on


def labels_shifted(fluid, causal_lm, moe):
    """Row i is held to the NEXT position's clean id, as the next-token
    objective shifts its labels."""
    import jax.numpy as jnp

    def shifted(ins, attrs):
        ins["Label"] = [jnp.roll(ins["Label"][0], -1, axis=0)]
        return ins, attrs
    _rule_with("softmax_with_cross_entropy", shifted)


def _weight_with(fluid, change):
    """The `loss_weight` feed as the loss reads it, behind `change(w)`."""
    layers, data = fluid.layers, fluid.layers.data

    def fed(name, *a, **kw):
        var = data(name, *a, **kw)
        return change(var) if name == "loss_weight" else var
    layers.data = fed


def loss_unweighted(fluid, causal_lm, moe):
    """Every masked position weighs 1, not 1 / t."""
    _weight_with(fluid, lambda w: fluid.layers.clip(
        fluid.layers.scale(w, scale=1e6), min=0.0, max=1.0))


def loss_on_every_position(fluid, causal_lm, moe):
    """A position that was not masked carries a loss of weight 1."""
    _weight_with(fluid, lambda w: fluid.layers.clip(w, min=1.0, max=1e9))


def loss_over_masked_count(fluid, causal_lm, moe):
    """The weighted sum is divided by the count of masked positions, not by
    the sequence's T tokens."""
    layers, mean = fluid.layers, fluid.layers.mean
    kept = {}

    def keep(w):
        kept["masked"] = layers.clip(layers.scale(w, scale=1e6), min=0.0,
                                     max=1.0)
        return w
    _weight_with(fluid, keep)
    layers.mean = lambda x, **kw: mean(x, **kw) / mean(kept["masked"])


def head_on_clean_rows(fluid, causal_lm, moe):
    """The last layer takes the CLEAN copy's rows behind its core: the head
    and the loss read rows that saw the answer."""
    layers, crop = fluid.layers, fluid.layers.crop

    def clean(x, shape, offsets=None, **kw):
        if offsets is None and len(shape) > 1 and shape[1] > 0 \
                and int(x.shape[1]) == 2 * shape[1]:
            offsets = [0, shape[1]] + [0] * (len(shape) - 2)
        return crop(x, shape, offsets, **kw)
    layers.crop = clean


def mask_id_off_by_one(fluid, causal_lm, moe):
    """A masked position embeds the word BEFORE the mask id."""
    import jax.numpy as jnp
    from benchmark import manifest
    found = {}
    load = manifest.load_cell

    def remember(*a, **kw):
        cell = load(*a, **kw)
        found["mask"] = cell.config["mask_token_id"]
        return cell
    manifest.load_cell = remember

    def other_word(ins, attrs):
        ids = ins["Ids"][0]
        ins["Ids"] = [jnp.where(ids == found["mask"], ids - 1, ids)]
        return ins, attrs
    _rule_with("lookup_table", other_word)


def qk_norm_dropped(fluid, causal_lm, moe):
    """q and k reach the rotary as the projections give them (the norms'
    weights stay parameters with a gradient of 0)."""
    norm = causal_lm._norm

    def unnormed(x, c, role=None):
        out = norm(x, c, role)
        if (role or c.get("role")) in ("q_norm", "k_norm"):
            return x + fluid.layers.scale(out, scale=0.0)
        return out
    causal_lm._norm = unnormed


def qk_norm_all_channels(fluid, causal_lm, moe):
    """The QK-norm's statistics run over ALL of a row's heads (OLMoE's
    form), under the weight a head: a head's size is no longer its own."""
    layers, norm = fluid.layers, causal_lm._norm

    def over_all(x, c, role=None):
        out = norm(x, c, role)
        if (role or c.get("role")) not in ("q_norm", "k_norm"):
            return out
        heads, hd = int(x.shape[2]), int(x.shape[3])
        flat = layers.reshape(x, shape=[0, -1, heads * hd])
        size = layers.reduce_mean(layers.square(layers.cast(
            flat, "float32")), dim=-1, keep_dim=True)
        size = layers.reshape(layers.cast(layers.sqrt(layers.scale(
            size, scale=1.0, bias=c["rms_norm_eps"])), x.dtype),
            shape=[0, -1, 1, 1])
        own = layers.reduce_mean(layers.square(layers.cast(
            x, "float32")), dim=-1, keep_dim=True)
        own = layers.cast(layers.sqrt(layers.scale(
            own, scale=1.0, bias=c["rms_norm_eps"])), x.dtype)
        # w x / rms_all = (w x / rms_head) x rms_head / rms_all
        return out * own / size
    causal_lm._norm = over_all


def _moe_ffn_with(fluid, **changed):
    ffn = fluid.layers.moe_ffn
    fluid.layers.moe_ffn = lambda *a, **kw: ffn(*a, **dict(kw, **changed))


def renorm_dropped(fluid, causal_lm, moe):
    """The chosen probabilities weigh the experts as they are, not divided
    by their sum."""
    _moe_ffn_with(fluid, norm_topk_prob=False)


def top_k_7(fluid, causal_lm, moe):
    """A row goes to 7 experts, not 8. `dropless` fails too."""
    routed = moe.routed_ffn
    moe.routed_ffn = lambda *a, top_k, **kw: routed(*a, top_k=top_k - 1,
                                                    **kw)


def kv_group_of_4(fluid, causal_lm, moe):
    """Query head h reads key/value head (h // 4) % 4, groups of 4 and not
    of 8, on both attention paths."""
    import importlib
    import jax.numpy as jnp
    from paddle_tpu.ops import pallas_kernels
    ring = importlib.import_module("paddle_tpu.parallel.ring_attention")

    def misread(attend):
        def broken(q, k, v, **kw):
            heads, kv = q.shape[2], k.shape[2]
            group = max(heads // kv // 2, 1)
            read = (jnp.arange(heads) // group) % kv
            return attend(q, jnp.take(k, read, axis=2),
                          jnp.take(v, read, axis=2), **kw)
        return broken
    pallas_kernels.flash_attention = misread(pallas_kernels.flash_attention)
    ring.attention_reference = misread(ring.attention_reference)


def experts_17_to_32_held(fluid, causal_lm, moe):
    """The chip computes the assignments of the router's columns 16 .. 31
    (with the weights it holds), the wrong share of the 128: chip 1's."""
    def next_share(ins, attrs):
        attrs["first_expert"] = ins["WUp"][0].shape[0]
        return ins, attrs
    _rule_with("moe_ffn", next_share)


def _reference_with(round_weights):
    """Wraps the configuration module's `reference` as it is loaded."""
    from benchmark import manifest
    load = manifest.load_module

    def load_and_wrap(path):
        mod = load(path)
        if path.endswith(os.path.join("configs", "sdar.py")):
            reference = mod.reference
            mod.reference = lambda cfg, traffic, params, batch: reference(
                cfg, traffic, [round_weights(p) for p in params], batch)
        return mod
    manifest.load_module = load_and_wrap


def reference_bf16_weights(fluid, causal_lm, moe):
    """The reference with its weights rounded to bfloat16: stays correct."""
    import jax.numpy as jnp
    _reference_with(lambda p: p.astype(jnp.bfloat16).astype(jnp.float32))


def reference_fp8_weights(fluid, causal_lm, moe):
    """The reference with its weights rounded to float8 e4m3, scaled a
    tensor to the format's range: has to fail a tolerance."""
    import jax.numpy as jnp

    def fp8(p):
        scale = jnp.abs(p).max() / 448.0
        return (p / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    _reference_with(fp8)


MUTANTS = {f.__name__: f for f in (
    mask_row_causal, clean_sees_noised, noised_sees_own_clean_block,
    noised_blind_to_clean, block_diagonal_one_way, clean_row_causal,
    block_length_8, positions_run_on, labels_shifted, loss_unweighted,
    loss_on_every_position, loss_over_masked_count, head_on_clean_rows,
    mask_id_off_by_one, qk_norm_dropped, qk_norm_all_channels,
    renorm_dropped, top_k_7, kv_group_of_4, experts_17_to_32_held,
    reference_bf16_weights, reference_fp8_weights)}
# those whose last line has to say `"correct": false`
HAVE_TO_FAIL = tuple(name for name in MUTANTS
                     if name != "reference_bf16_weights")


def main(argv):
    if not argv or argv[0] not in MUTANTS:
        print("usage: mutant_sdar.py <%s> <arguments of benchmark/run.py>"
              % "|".join(MUTANTS), file=sys.stderr)
        return 1
    import paddle_tpu as fluid
    from paddle_tpu.models import causal_lm
    from paddle_tpu.parallel import moe
    MUTANTS[argv[0]](fluid, causal_lm, moe)
    print("bench: MUTANT %s: %s" % (argv[0], MUTANTS[argv[0]].__doc__),
          flush=True)
    from benchmark import run
    return run.main(argv[1:])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
