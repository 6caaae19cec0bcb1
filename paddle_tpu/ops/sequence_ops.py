"""Sequence / recurrent op lowerings over the padded-dense layout.

Parity: paddle/fluid/operators/{sequence_pool_op,sequence_softmax_op,
sequence_conv_op,sequence_expand_op,sequence_reshape_op,lod_reset_op,
lstm_op,gru_op,row_conv_op}.{cc,cu,h}.

Layout contract (SURVEY.md §6.3): a lod_level-1 tensor is a padded dense
array X [num_seqs, max_len, *feature] plus XLen int32 [num_seqs] of true
lengths. The reference walks host-side LoD offsets per op; here every op is
a masked/vectorized XLA computation with static shapes. The recurrences
(dynamic_lstm/dynamic_gru) are lax.scan over time with the gate matmuls
batched onto the MXU.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..core.registry import register, single
from .kernel_config import pallas_on


def _mask(xlen, max_len, dtype=jnp.float32):
    """[B, T] 1/0 validity mask from lengths."""
    t = jnp.arange(max_len, dtype=jnp.int32)
    return (t[None, :] < xlen.astype(jnp.int32)[:, None]).astype(dtype)


def _feat_mask(x, xlen):
    """mask broadcastable over x's feature dims."""
    m = _mask(xlen, x.shape[1], x.dtype)
    return m.reshape(m.shape + (1,) * (x.ndim - 2))


@register("sequence_pool", calls_pallas=True)
def _sequence_pool(ctx, ins, attrs):
    x = single(ins, "X")          # [B, T, ...]
    xlen = single(ins, "XLen")    # [B]
    ptype = attrs.get("pooltype", "AVERAGE").upper()
    # fused path gates on f32 like the LSTM kernel: the kernel computes
    # in f32, so an int accumulation (exact in the dense path) or a
    # bf16 input must not silently change numerics under the flag
    if ptype in ("SUM", "AVERAGE", "SQRT") and x.ndim >= 2 \
            and x.dtype == jnp.float32 and pallas_on("seq"):
        # fused masked pool: one VMEM pass builds the @SEQLEN mask and
        # reduces (linear pools only — MAX/LAST/FIRST keep the dense
        # path). Feature dims flatten to one trailing axis.
        from . import pallas_kernels as pk
        b, t = x.shape[:2]
        feat = x.shape[2:]
        f = int(np.prod(feat)) if feat else 1
        out = pk.masked_pool(
            x.reshape(b, t, f), xlen, ptype=ptype).reshape((b,) + feat)
        return {"Out": [out.astype(x.dtype)]}
    m = _feat_mask(x, xlen)
    denom = jnp.maximum(xlen.astype(x.dtype), 1).reshape(
        (-1,) + (1,) * (x.ndim - 2))
    if ptype == "SUM":
        out = jnp.sum(x * m, axis=1)
    elif ptype == "AVERAGE":
        out = jnp.sum(x * m, axis=1) / denom
    elif ptype == "SQRT":
        out = jnp.sum(x * m, axis=1) / jnp.sqrt(denom)
    elif ptype == "MAX":
        neg = jnp.asarray(jnp.finfo(x.dtype).min, x.dtype)
        out = jnp.max(jnp.where(m > 0, x, neg), axis=1)
    elif ptype == "LAST":
        idx = jnp.maximum(xlen.astype(jnp.int32) - 1, 0)
        out = jnp.take_along_axis(
            x, idx.reshape((-1, 1) + (1,) * (x.ndim - 2)), axis=1
        ).squeeze(1)
    elif ptype == "FIRST":
        out = x[:, 0]
    else:
        raise ValueError("unknown pooltype %r" % ptype)
    # MaxIndex output (reference) only needed for MAX grad — vjp handles it
    return {"Out": [out]}


@register("sequence_last_step")
def _sequence_last_step(ctx, ins, attrs):
    return _sequence_pool(ctx, ins, dict(attrs, pooltype="LAST"))


@register("sequence_first_step")
def _sequence_first_step(ctx, ins, attrs):
    return _sequence_pool(ctx, ins, dict(attrs, pooltype="FIRST"))


@register("sequence_softmax", calls_pallas=True)
def _sequence_softmax(ctx, ins, attrs):
    x = single(ins, "X")        # [B, T] or [B, T, 1]
    xlen = single(ins, "XLen")
    squeeze = x.ndim == 3 and x.shape[-1] == 1
    logits = x.reshape(x.shape[0], x.shape[1]) if squeeze else x
    if logits.ndim == 2 and logits.dtype == jnp.float32 \
            and pallas_on("seq"):
        # fused masked softmax: mask + online max + normalize in one
        # VMEM pass per row block (bit-exact vs the where-mask path:
        # masked lanes underflow exp to exactly 0 either way)
        from . import pallas_kernels as pk
        out = pk.masked_softmax(logits, xlen)
        if squeeze:
            out = out.reshape(x.shape)
        return {"Out": [out.astype(x.dtype)]}
    m = _mask(xlen, logits.shape[1], logits.dtype)
    neg = jnp.asarray(-1e30, logits.dtype)
    out = jax.nn.softmax(jnp.where(m > 0, logits, neg), axis=1) * m
    if squeeze:
        out = out.reshape(x.shape)
    return {"Out": [out]}


@register("sequence_conv")
def _sequence_conv(ctx, ins, attrs):
    """Context-window conv over time (reference: sequence_conv_op).

    Filter [ctx_len * D, F]; context window centered per contextStart.
    """
    x = single(ins, "X")         # [B, T, D]
    w = single(ins, "Filter")    # [ctx_len*D, F]
    xlen = single(ins, "XLen")
    ctx_len = attrs.get("contextLength", 3)
    ctx_start = attrs.get("contextStart", -(ctx_len // 2))
    b, t, d = x.shape
    xm = x * _feat_mask(x, xlen)
    cols = []
    for k in range(ctx_len):
        off = ctx_start + k
        shifted = jnp.roll(xm, -off, axis=1)
        if off > 0:    # rolled forward: zero the tail
            valid = jnp.arange(t) < (t - off)
        elif off < 0:  # rolled backward: zero the head
            valid = jnp.arange(t) >= (-off)
        else:
            valid = jnp.ones(t, bool)
        cols.append(shifted * valid[None, :, None].astype(x.dtype))
    ctx_mat = jnp.concatenate(cols, axis=-1)        # [B, T, ctx_len*D]
    out = jnp.einsum("btc,cf->btf", ctx_mat, w)
    out = out * _feat_mask(out, xlen)
    return {"Out": [out]}


@register("sequence_reshape")
def _sequence_reshape(ctx, ins, attrs):
    """Repack row data to width new_dim (reference: sequence_reshape_op.cc).

    Padded-dense: each row's valid data is a contiguous prefix of the
    flattened [T*D] row, so reshaping to [T*D/new_dim, new_dim] keeps it a
    contiguous prefix; only the lengths rescale (exact integer math). T is
    zero-padded up when T*D doesn't divide new_dim (bucketed padding)."""
    x = single(ins, "X")        # [B, T, D]
    xlen = single(ins, "XLen")  # [B]
    new_dim = int(attrs["new_dim"])
    b, t, d = x.shape
    # smallest pad with (t+pad)*d % new_dim == 0: t+pad ≡ 0 (mod nd/gcd)
    import math
    m = new_dim // math.gcd(d, new_dim)
    pad_t = (-t) % m
    if pad_t:
        x = jnp.pad(x, ((0, 0), (0, pad_t), (0, 0)))
        t += pad_t
    out = x.reshape(b, (t * d) // new_dim, new_dim)
    elems = xlen.astype(jnp.int32) * d
    # reference sequence_reshape_op.cc enforces per-sequence divisibility;
    # a floor here would silently drop the tail of a sequence
    ctx.add_error(
        "sequence_reshape: a sequence's len*dim (%d per step) is not "
        "divisible by new_dim=%d; its tail would be dropped" % (d, new_dim),
        (elems % new_dim != 0).any())
    out_len = elems // new_dim
    return {"Out": [out], "OutLen": [out_len]}


@register("sequence_expand")
def _sequence_expand(ctx, ins, attrs):
    """Expand each row of X to match Y's sequence lengths.

    Padded-layout semantics: X [B, 1-or-T, ...] or [B, ...]; output repeats
    X's per-sequence row across Y's max_len timesteps (masked).
    """
    x = single(ins, "X")
    y = single(ins, "Y")
    ylen = single(ins, "YLen")
    t = y.shape[1]
    if x.ndim == y.ndim:          # padded [B, Tx, ...]: row 0 is the entry
        head = x[:, 0]
    else:                          # [B, ...] per-sequence row
        head = x
    rep = jnp.broadcast_to(head[:, None], (x.shape[0], t) + head.shape[1:])
    return {"Out": [rep * _feat_mask(rep, ylen)]}


@register("lod_reset")
def _lod_reset(ctx, ins, attrs):
    """lod_reset_op.cc: keep the flat data stream, replace the segmentation.

    The reference's row-major [total, D] layout makes this metadata-only;
    the padded-dense layout has to repack rows — flatten X's valid rows to
    a contiguous stream (scatter by old cumulative lengths), then re-split
    per the new lengths (gather by new cumulative lengths). New lengths
    come from attr target_lens (static), YLen (Y's own LoD), or YData
    (Y.data holding offsets, reference doc "attr(target_lod): [0, 4, 6]").
    """
    x = single(ins, "X")
    xlen = single(ins, "XLen")
    ylen = single(ins, "YLen")
    ydata = single(ins, "YData")
    y = single(ins, "Y")
    t_lens = attrs.get("target_lens") or []
    if ylen is None and ydata is None and not t_lens:
        # no target: pass through unchanged (the reference op enforces a
        # target; tolerated here for metadata-only program clones)
        return {"Out": [x]} if xlen is None else \
            {"Out": [x], "OutLen": [xlen]}
    # 1. flatten valid rows into one contiguous stream
    if xlen is not None:
        b, t = x.shape[:2]
        feat = x.shape[2:]
        cap = b * t
        xl = xlen.astype(jnp.int32)
        cum = jnp.cumsum(xl) - xl                       # exclusive prefix
        pos = cum[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        valid = jnp.arange(t, dtype=jnp.int32)[None, :] < xl[:, None]
        pos = jnp.where(valid, pos, cap)                # park padding rows
        flat = jnp.zeros((cap + 1,) + feat, x.dtype).at[
            pos.reshape(-1)].set(x.reshape((cap,) + feat))[:cap]
    else:                                               # dense X: rows ARE the stream
        feat = x.shape[1:]
        flat = x
        cap = x.shape[0]
    # 2. new segmentation
    if ylen is not None:
        newlen = ylen.astype(jnp.int32)
        b2 = y.shape[0] if y is not None else newlen.shape[0]
        t2 = y.shape[1] if y is not None and len(y.shape) > 1 else cap
    elif ydata is not None:
        off = ydata.reshape(-1).astype(jnp.int32)
        newlen = off[1:] - off[:-1]
        b2, t2 = newlen.shape[0], cap
    else:
        lens = [int(v) for v in t_lens]
        newlen = jnp.asarray(lens, jnp.int32)
        b2, t2 = len(lens), max(lens)
    # reference lod_reset_op.cc enforces an ascending LoD whose last offset
    # equals the data length; a mismatch here would silently duplicate
    # (clip) or drop rows. Non-monotone offsets telescope to a valid sum,
    # so negative lengths must be rejected separately.
    total = jnp.sum(xl) if xlen is not None else cap
    ctx.add_error(
        "lod_reset: target segmentation length sum != data stream length",
        (jnp.sum(newlen) != total) | (newlen < 0).any())
    cum2 = jnp.cumsum(newlen) - newlen
    idx = cum2[:, None] + jnp.arange(t2, dtype=jnp.int32)[None, :]
    valid2 = jnp.arange(t2, dtype=jnp.int32)[None, :] < newlen[:, None]
    out = flat[jnp.clip(idx, 0, cap - 1).reshape(-1)].reshape(
        (b2, t2) + feat)
    out = jnp.where(valid2.reshape((b2, t2) + (1,) * len(feat)), out,
                    jnp.zeros((), x.dtype))
    return {"Out": [out], "OutLen": [newlen]}


@register("row_conv")
def _row_conv(ctx, ins, attrs):
    """Lookahead row convolution (reference: row_conv_op, DeepSpeech2)."""
    x = single(ins, "X")        # [B, T, D]
    w = single(ins, "Filter")   # [future_ctx, D]
    xlen = single(ins, "XLen")
    fut = w.shape[0]
    xm = x * _feat_mask(x, xlen)
    out = jnp.zeros_like(x)
    t = x.shape[1]
    for k in range(fut):
        shifted = jnp.roll(xm, -k, axis=1)
        valid = (jnp.arange(t) < (t - k)).astype(x.dtype)
        out = out + shifted * valid[None, :, None] * w[k][None, None, :]
    return {"Out": [out * _feat_mask(x, xlen)]}


# ---------------------------------------------------------------------------
# recurrences: LSTM / GRU via lax.scan (reference: lstm_op.cc, gru_op.cc —
# there a C++ loop over LoD-sorted batches calling cuBLAS per step; here one
# scan whose per-step gate matmul is a single MXU batched matmul)
# ---------------------------------------------------------------------------

def _lstm_act(name):
    return {"sigmoid": jax.nn.sigmoid, "tanh": jnp.tanh,
            "relu": jax.nn.relu, "identity": lambda v: v}[name]


def _amp_recurrence(ctx, x_dtype):
    """AMP discipline for scan recurrences: the per-step gate matmul rides
    the MXU in bf16 (2x fp32 throughput), but the carried state accumulates
    in f32 — carrying cell state in bf16 loses the long-horizon additions
    that make LSTMs work. Applies when the program is AMP or the input
    already arrived bf16 (from an AMP'd input-projection mul).

    Returns (state_dtype, rmat(h, w)) — shared by _lstm and _gru."""
    bf = getattr(ctx, "amp", False) or x_dtype == jnp.bfloat16
    state_dt = jnp.float32 if x_dtype in (jnp.float32, jnp.bfloat16) \
        else x_dtype

    def rmat(h, wm):
        if bf:
            return jnp.matmul(h.astype(jnp.bfloat16),
                              wm.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        return h @ wm.astype(state_dt)

    return state_dt, rmat


@register("lstm", calls_pallas=True)
def _lstm(ctx, ins, attrs):
    """dynamic_lstm: input [B, T, 4D] (pre-projected by an fc), weight
    [D, 4D] recurrent, bias [1, 4D] (+[1, 3D] peepholes if use_peepholes).

    Gate order (reference lstm_op.cc:125 {W_ch, W_ih, W_fh, W_oh}):
    candidate, input, forget, output.
    """
    x = single(ins, "Input")       # [B, T, 4D]
    w = single(ins, "Weight")      # [D, 4D]
    bias = single(ins, "Bias")     # [1, 4D(+3D)]
    h0 = single(ins, "H0")
    c0 = single(ins, "C0")
    xlen = single(ins, "XLen")
    d = w.shape[0]
    b, t, _ = x.shape
    use_peep = attrs.get("use_peepholes", False)
    gact = _lstm_act(attrs.get("gate_activation", "sigmoid"))
    # lstm_op.h: act_cand maps the candidate gate, act_cell maps the cell
    # state on its way into the hidden output (h = o * act_cell(c)) —
    # indistinguishable at the tanh/tanh default, distinct otherwise
    cell_act = _lstm_act(attrs.get("cell_activation", "tanh"))
    cand_act = _lstm_act(attrs.get("candidate_activation", "tanh"))
    is_rev = attrs.get("is_reverse", False)

    if (not use_peep and x.dtype == jnp.float32
            and not getattr(ctx, "amp", False)
            and attrs.get("gate_activation", "sigmoid") == "sigmoid"
            and attrs.get("cell_activation", "tanh") == "tanh"
            and attrs.get("candidate_activation", "tanh") == "tanh"
            and pallas_on("lstm")):
        # fused pallas recurrence (default activations, no peepholes —
        # the long tail keeps the scan): four gates + state update in
        # one VMEM pass per step, carried state resident in VMEM
        from . import pallas_kernels as pk
        hidden, cell = pk.fused_lstm(
            x, w, bias.reshape(-1)[:4 * d], h0, c0, xlen, reverse=is_rev)
        return {"Hidden": [hidden], "Cell": [cell],
                "BatchGate": [x], "BatchCellPreAct": [cell]}

    state_dt, rmat2 = _amp_recurrence(ctx, x.dtype)
    rmat = lambda h: rmat2(h, w)

    bias = bias.reshape(-1).astype(state_dt)
    gate_bias = bias[:4 * d]
    if use_peep:
        w_ic, w_fc, w_oc = (bias[4 * d:5 * d], bias[5 * d:6 * d],
                            bias[6 * d:7 * d])
    h_prev = h0.astype(state_dt) if h0 is not None \
        else jnp.zeros((b, d), state_dt)
    c_prev = c0.astype(state_dt) if c0 is not None \
        else jnp.zeros((b, d), state_dt)

    m = _mask(xlen, t, state_dt)                    # [B, T]
    xs = jnp.swapaxes(x, 0, 1).astype(state_dt)     # [T, B, 4D]
    ms = m.T[:, :, None]                            # [T, B, 1]
    if is_rev:
        xs = xs[::-1]
        ms = ms[::-1]

    def step(carry, inp):
        h_prev, c_prev = carry
        xt, mt = inp
        gates = xt + rmat(h_prev) + gate_bias       # [B, 4D]
        # reference weight layout lstm_op.cc:125 "{W_ch, W_ih, W_fh,
        # W_oh}" — CANDIDATE block first (kernel order in, ig, fg, og)
        gc, gi, gf, go = jnp.split(gates, 4, axis=-1)
        if use_peep:
            gi = gi + c_prev * w_ic
            gf = gf + c_prev * w_fc
        i = gact(gi)
        f = gact(gf)
        c_new = f * c_prev + i * cand_act(gc)
        if use_peep:
            go = go + c_new * w_oc
        o = gact(go)
        h_new = o * cell_act(c_new)
        # masked carry: padding steps keep previous state
        h = mt * h_new + (1 - mt) * h_prev
        c = mt * c_new + (1 - mt) * c_prev
        return (h, c), (h, c)

    (hT, cT), (hs, cs) = lax.scan(step, (h_prev, c_prev), (xs, ms))
    if is_rev:
        hs, cs = hs[::-1], cs[::-1]
    hidden = jnp.swapaxes(hs, 0, 1).astype(x.dtype)  # [B, T, D]
    cell = jnp.swapaxes(cs, 0, 1).astype(x.dtype)
    return {"Hidden": [hidden], "Cell": [cell],
            "BatchGate": [x], "BatchCellPreAct": [cell]}


@register("lstmp", calls_pallas=True)
def _lstmp(ctx, ins, attrs):
    """lstmp_op.cc — LSTM with recurrent projection: the [B, P] PROJECTED
    state (not the [B, D] hidden) feeds the next step's gate matmul
    (lstmp_op.h:161-167), so Weight is [P, 4D] and ProjWeight [D, P];
    r_t = proj_act(h_t @ ProjWeight). H0 [B, D] enters through the same
    projection (lstmp_op.h:174-187). Divergence kept deliberately: the
    reference gates on proj_act but then applies cell_act to the
    projection (lstmp_op.h:201-203, an evident typo since both default to
    tanh); we apply proj_act itself.
    """
    x = single(ins, "Input")            # [B, T, 4D]
    w = single(ins, "Weight")           # [P, 4D]
    w_proj = single(ins, "ProjWeight")  # [D, P]
    bias = single(ins, "Bias")          # [1, 4D(+3D)]
    h0 = single(ins, "H0")
    c0 = single(ins, "C0")
    xlen = single(ins, "XLen")
    d = w_proj.shape[0]
    p = w_proj.shape[1]
    b, t, _ = x.shape
    use_peep = attrs.get("use_peepholes", False)
    gact = _lstm_act(attrs.get("gate_activation", "sigmoid"))
    cell_act = _lstm_act(attrs.get("cell_activation", "tanh"))
    cand_act = _lstm_act(attrs.get("candidate_activation", "tanh"))
    pact = _lstm_act(attrs.get("proj_activation", "tanh"))
    is_rev = attrs.get("is_reverse", False)

    if (not use_peep and x.dtype == jnp.float32
            and not getattr(ctx, "amp", False)
            and attrs.get("gate_activation", "sigmoid") == "sigmoid"
            and attrs.get("cell_activation", "tanh") == "tanh"
            and attrs.get("candidate_activation", "tanh") == "tanh"
            and attrs.get("proj_activation", "tanh") == "tanh"
            and pallas_on("lstm")):
        from . import pallas_kernels as pk
        if h0 is not None:
            r0 = jnp.tanh(h0.astype(jnp.float32) @
                          w_proj.astype(jnp.float32))
        else:
            r0 = jnp.zeros((b, p), jnp.float32)
        proj, cell = pk.fused_lstmp(
            x, w, w_proj, bias.reshape(-1)[:4 * d], r0, c0, xlen,
            reverse=is_rev)
        return {"Projection": [proj], "Cell": [cell],
                "BatchGate": [x], "BatchCellPreAct": [cell],
                "BatchHidden": [cell], "OrderedP0": [r0.astype(x.dtype)]}

    state_dt, rmat2 = _amp_recurrence(ctx, x.dtype)

    bias = bias.reshape(-1).astype(state_dt)
    gate_bias = bias[:4 * d]
    if use_peep:
        w_ic, w_fc, w_oc = (bias[4 * d:5 * d], bias[5 * d:6 * d],
                            bias[6 * d:7 * d])
    c_prev = c0.astype(state_dt) if c0 is not None \
        else jnp.zeros((b, d), state_dt)
    if h0 is not None:
        r_prev = pact(rmat2(h0.astype(state_dt), w_proj))
    else:
        r_prev = jnp.zeros((b, p), state_dt)

    m = _mask(xlen, t, state_dt)
    xs = jnp.swapaxes(x, 0, 1).astype(state_dt)     # [T, B, 4D]
    ms = m.T[:, :, None]
    if is_rev:
        xs = xs[::-1]
        ms = ms[::-1]

    def step(carry, inp):
        r_prev, c_prev = carry
        xt, mt = inp
        gates = xt + rmat2(r_prev, w) + gate_bias    # [B, 4D]
        # reference weight layout lstm_op.cc:125 "{W_ch, W_ih, W_fh,
        # W_oh}" — CANDIDATE block first (kernel order in, ig, fg, og)
        gc, gi, gf, go = jnp.split(gates, 4, axis=-1)
        if use_peep:
            gi = gi + c_prev * w_ic
            gf = gf + c_prev * w_fc
        i = gact(gi)
        f = gact(gf)
        c_new = f * c_prev + i * cand_act(gc)
        if use_peep:
            go = go + c_new * w_oc
        o = gact(go)
        h_new = o * cell_act(c_new)
        r_new = pact(rmat2(h_new, w_proj))           # [B, P]
        r = mt * r_new + (1 - mt) * r_prev
        c = mt * c_new + (1 - mt) * c_prev
        return (r, c), (r, c)

    _, (rs, cs) = lax.scan(step, (r_prev, c_prev), (xs, ms))
    if is_rev:
        rs, cs = rs[::-1], cs[::-1]
    proj = jnp.swapaxes(rs, 0, 1).astype(x.dtype)   # [B, T, P]
    cell = jnp.swapaxes(cs, 0, 1).astype(x.dtype)
    return {"Projection": [proj], "Cell": [cell],
            "BatchGate": [x], "BatchCellPreAct": [cell],
            "BatchHidden": [cell], "OrderedP0": [r_prev]}


@register("gru")
def _gru(ctx, ins, attrs):
    """dynamic_gru: input [B, T, 3D] pre-projected, weight packed
    [D, 3D] = [update|reset (2D) ; candidate (D)] as in gru_op.cc.
    """
    x = single(ins, "Input")     # [B, T, 3D]
    w = single(ins, "Weight")    # [D, 3D]
    bias = single(ins, "Bias")   # [1, 3D]
    h0 = single(ins, "H0")
    xlen = single(ins, "XLen")
    d = w.shape[0]
    b, t, _ = x.shape
    gact = _lstm_act(attrs.get("gate_activation", "sigmoid"))
    cact = _lstm_act(attrs.get("activation", "tanh"))
    is_rev = attrs.get("is_reverse", False)

    state_dt, rmat = _amp_recurrence(ctx, x.dtype)

    w_g = w[:, :2 * d]      # update+reset recurrent weights
    w_c = w[:, 2 * d:]      # candidate recurrent weights
    bias = bias.reshape(-1).astype(state_dt) if bias is not None \
        else jnp.zeros(3 * d, state_dt)
    h_prev = h0.astype(state_dt) if h0 is not None \
        else jnp.zeros((b, d), state_dt)

    m = _mask(xlen, t, state_dt)
    xs = jnp.swapaxes(x, 0, 1).astype(state_dt)
    ms = m.T[:, :, None]
    if is_rev:
        xs = xs[::-1]
        ms = ms[::-1]

    def step(h_prev, inp):
        xt, mt = inp
        xu = xt[:, :2 * d] + rmat(h_prev, w_g) + bias[:2 * d]
        u, r = jnp.split(gact(xu), 2, axis=-1)
        c = cact(xt[:, 2 * d:] + rmat(r * h_prev, w_c) + bias[2 * d:])
        # reference gru convention (gru_kernel.h / test_gru_op.py:71):
        # the update gate weights the CANDIDATE, not the carried state
        h_new = u * c + (1 - u) * h_prev
        h = mt * h_new + (1 - mt) * h_prev
        return h, h

    hT, hs = lax.scan(step, h_prev, (xs, ms))
    if is_rev:
        hs = hs[::-1]
    hidden = jnp.swapaxes(hs, 0, 1).astype(x.dtype)
    return {"Hidden": [hidden], "BatchGate": [x],
            "BatchResetHiddenPrev": [hidden], "BatchHidden": [hidden]}


@register("gru_unit")
def _gru_unit(ctx, ins, attrs):
    """Single GRU step (reference: gru_unit_op) — used inside DynamicRNN."""
    x = single(ins, "Input")        # [B, 3D]
    h_prev = single(ins, "HiddenPrev")
    w = single(ins, "Weight")       # [D, 3D]
    bias = single(ins, "Bias")
    d = w.shape[0]
    gact = _lstm_act({1: "sigmoid", 0: "identity", 2: "tanh",
                      3: "relu"}.get(attrs.get("gate_activation", 1),
                                     "sigmoid")
                     if isinstance(attrs.get("gate_activation", 1), int)
                     else attrs.get("gate_activation", "sigmoid"))
    cact = _lstm_act({1: "sigmoid", 0: "identity", 2: "tanh",
                      3: "relu"}.get(attrs.get("activation", 2), "tanh")
                     if isinstance(attrs.get("activation", 2), int)
                     else attrs.get("activation", "tanh"))
    if bias is not None:
        x = x + bias.reshape(-1)
    xu = x[:, :2 * d] + h_prev @ w[:, :2 * d]
    u, r = jnp.split(gact(xu), 2, axis=-1)
    c = cact(x[:, 2 * d:] + (r * h_prev) @ w[:, 2 * d:])
    h = u * c + (1 - u) * h_prev   # gru_unit_op: u weights the candidate
    return {"Hidden": [h], "Gate": [xu], "ResetHiddenPrev": [r * h_prev]}


@register("lstm_unit")
def _lstm_unit(ctx, ins, attrs):
    """Single LSTM step (reference: lstm_unit_op): X [B, 4D] pre-gates."""
    x = single(ins, "X")
    c_prev = single(ins, "C_prev")
    forget_bias = attrs.get("forget_bias", 0.0)
    # reference lstm_unit_op.h packs gates i, f, o, j — candidate LAST
    # (unlike lstm_op's candidate-FIRST {W_ch, W_ih, W_fh, W_oh}) —
    # order matters for loaded weights
    gi, gf, go, gj = jnp.split(x, 4, axis=-1)
    i = jax.nn.sigmoid(gi)
    f = jax.nn.sigmoid(gf + forget_bias)
    o = jax.nn.sigmoid(go)
    c = f * c_prev + i * jnp.tanh(gj)
    h = o * jnp.tanh(c)
    return {"C": [c], "H": [h]}


@register("sequence_cache_write")
def _sequence_cache_write(ctx, ins, attrs):
    """Per-row timestep write into a [B, T, ...] cache (TPU-native
    addition): Out[b, Pos[b]] = X[b], every other cell bit-identical to
    Cache.  The KV-cache building block for decode-step programs —
    Cache and Pos are persistable slot state under serving.DecodeEngine,
    so the executor's donation machinery keeps the whole cache
    device-resident and this lowers to one in-place scatter row write
    per step, never a host round-trip or a full-cache copy.  Row b's
    output depends only on row b of every input — the property the
    decode batcher's slot-reuse invariant (ARCHITECTURE §27) leans on."""
    cache = single(ins, "Cache")                      # [B, T, ...]
    x = single(ins, "X")                              # [B, ...]
    pos = single(ins, "Pos").astype(jnp.int32).reshape(-1)   # [B]
    b = cache.shape[0]
    out = cache.at[jnp.arange(b), pos].set(jnp.asarray(x, cache.dtype))
    return {"Out": [out]}


@register("sequence_mask")
def _sequence_mask(ctx, ins, attrs):
    """lengths [N] -> [N, maxlen] mask. Parity: sequence_mask_op.h."""
    x = single(ins, "X").astype(jnp.int32)
    ref = single(ins, "MaxLenRef")
    maxlen = ref.shape[1] if ref is not None else int(attrs["maxlen"])
    t = jnp.arange(maxlen, dtype=jnp.int32)
    mask = (t[None, :] < x[:, None])
    return {"Y": [mask.astype(np.dtype(attrs.get("out_dtype", "int64")))]}
