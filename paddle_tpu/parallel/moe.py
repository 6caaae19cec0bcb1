"""Expert parallelism (`ep` mesh axis): mixture-of-experts FFN.

TPU-first addition (the reference predates MoE entirely; SURVEY §2 commits
to DP/TP/PP/SP/EP composable on one Mesh). The design is the classic
static-shape TPU MoE (Shazeer-style dense dispatch, the pattern GShard
popularized): top-1 gating, fixed expert capacity, and dispatch/combine as
one-hot einsums — no ragged shapes, no host-side routing. Under GSPMD the
expert dim of the weights and the [E, C, D] dispatched activations are
sharded P('ep'); XLA lowers the dispatch einsum to the all-to-all over ICI,
exactly as a hand-written collective would, but fused and overlapped.

Everything is a pure jax function over an explicit params pytree —
differentiable, jit/pjit-friendly, composable with dp on the same mesh.

`routed_ffn` (the `moe_ffn` op) is the other design, for one chip today:
top-k routing without capacity, the assignments sorted by expert and the
expert matmuls grouped over them, so no token is dropped and no operation
is spent on an expert a token did not choose. It can be told which experts
it holds: it then routes over all of them and computes its own share of the
layer's output, as one chip of an expert-parallel layer would, without the
exchange. Its row buffer (top_k * N rows) is numbered slot-major, so that a
token's slots are summed without a relayout, and of the sorted rows only the
tiles that hold a held assignment are gathered (`_dispatch`, `_combine`).
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from .mesh import P, NamedSharding

__all__ = ["init_moe_params", "moe_layer", "moe_param_specs",
           "dense_reference", "routed_ffn"]


def init_moe_params(rng, d_model, d_hidden, num_experts, dtype="float32"):
    """params = {gate [D,E], w1 [E,D,H], b1 [E,H], w2 [E,H,D], b2 [E,D]}."""
    k = [rng.randn(d_model, num_experts) * 0.02,
         rng.randn(num_experts, d_model, d_hidden) * (d_model ** -0.5),
         np.zeros((num_experts, d_hidden)),
         rng.randn(num_experts, d_hidden, d_model) * (d_hidden ** -0.5),
         np.zeros((num_experts, d_model))]
    names = ["gate", "w1", "b1", "w2", "b2"]
    return {n: jnp.asarray(a, dtype) for n, a in zip(names, k)}


def moe_param_specs(axis="ep"):
    """PartitionSpecs: experts sharded over `axis`, gate replicated."""
    return {"gate": P(), "w1": P(axis), "b1": P(axis),
            "w2": P(axis), "b2": P(axis)}


def dense_reference(params, x):
    """Per-token expert compute without capacity limits (the semantics the
    capacity-bounded fast path approaches as capacity grows)."""
    logits = x @ params["gate"]                      # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)              # [N]
    top_p = jnp.max(probs, axis=-1)                  # [N]
    h = jnp.einsum("nd,edh->neh", x, params["w1"]) + params["b1"]
    h = jax.nn.relu(h)
    y = jnp.einsum("neh,ehd->ned", h, params["w2"]) + params["b2"]
    y_sel = jnp.take_along_axis(
        y, expert[:, None, None].repeat(y.shape[-1], -1), axis=1)[:, 0]
    return y_sel * top_p[:, None]


def moe_layer(params, x, capacity_factor=1.25, mesh=None, axis="ep"):
    """Top-1 MoE FFN over tokens x [N, D] -> ([N, D], aux_loss).

    Static shapes: each expert processes exactly C = ceil(N/E *
    capacity_factor) token slots; overflow tokens pass through with zero
    expert output (standard capacity dropping). aux_loss is the GShard
    load-balance term mean(fraction_tokens * fraction_probs) * E^2 — add
    a small multiple of it to the training loss to keep experts used.

    With `mesh` given, expert-dim intermediates are sharding-constrained to
    P(axis) so GSPMD dispatches tokens over the ep axis (all-to-all on
    ICI); without it the same code runs single-device.
    """
    n, d = x.shape
    e = params["w1"].shape[0]
    cap = int(np.ceil(n / e * capacity_factor))

    logits = x @ params["gate"]                      # [N, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)              # [N] int
    top_p = jnp.max(probs, axis=-1)                  # [N]

    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)   # [N, E]
    # position of each token within its expert's queue (0-based)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1.0  # [N]
    keep = pos < cap                                         # overflow drop
    pos_clip = jnp.clip(pos, 0, cap - 1).astype(jnp.int32)

    # dispatch/combine tensors (dense one-hots -> einsum == all_to_all)
    pos_onehot = jax.nn.one_hot(pos_clip, cap, dtype=jnp.float32)  # [N, C]
    dispatch = (onehot * keep[:, None])[:, :, None] * \
        pos_onehot[:, None, :]                               # [N, E, C]
    combine = dispatch * top_p[:, None, None]                # [N, E, C]

    expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                           x.astype(jnp.float32))            # [E, C, D]
    if mesh is not None:
        expert_in = jax.lax.with_sharding_constraint(
            expert_in, NamedSharding(mesh, P(axis)))
    h = jnp.einsum("ecd,edh->ech", expert_in, params["w1"].astype(
        jnp.float32)) + params["b1"].astype(jnp.float32)[:, None, :]
    h = jax.nn.relu(h)
    out = jnp.einsum("ech,ehd->ecd", h, params["w2"].astype(
        jnp.float32)) + params["b2"].astype(jnp.float32)[:, None, :]
    # bias must not leak into empty slots (combine handles weighting, but
    # b2 made empty slots nonzero only matters through combine=0 -> fine)
    if mesh is not None:
        out = jax.lax.with_sharding_constraint(
            out, NamedSharding(mesh, P(axis)))
    y = jnp.einsum("nec,ecd->nd", combine, out)              # [N, D]

    # load-balance aux loss (GShard eq. 4): encourages uniform routing
    frac_tokens = jnp.mean(onehot, axis=0)                   # [E]
    frac_probs = jnp.mean(probs, axis=0)                     # [E]
    aux = jnp.sum(frac_tokens * frac_probs) * e
    return y.astype(x.dtype), aux


# --- dropless top-k routed experts (the `moe_ffn` op) -----------------------
# The grouped-matmul route every expert matmul of `routed_ffn` takes, as the
# counter ptpu_moe_layers_total{path} names it.
GROUPED_MATMUL = "ragged_dot"


def _grouped_matmul(lhs, rhs, group_sizes):
    """lhs [M, K] rows sorted by group, rhs [G, K, N], group_sizes [G] with
    sum M: row m times its own group's matrix, [M, N] in lhs's dtype. Costs
    M x K x N multiply-adds whatever G is."""
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)


def rows_moved(experts, held):
    """Which rows of the top_k * N row buffer `routed_ffn`'s two expert-side
    gathers move, decided from the shapes: "all" where every expert is held
    (every row is in a group), "held" where a share is (the tiles below the
    held assignments' count, a run-time value)."""
    return "all" if held == experts else "held"


# Rows a trip of the held-rows loops moves; flat from 512 to 2048 (my chip run,
# PR 32). One SmallThinker layer forward and backward, 12426 of 49152 rows held,
# ms: 128 20.95, 256 20.60, 512 20.38, 1024 20.56, 2048 20.60, 4096 +0.7, 8192
# +1.2. The cell, tokens/s/chip over two seeds: 512 44,211, 1024 44,261, 2048
# 44,269, 4096 44,213. Half a tile is gathered for nothing; a trip costs little.
ROW_TILE = 1024


def _held_tiles(rows, total, one_tile, carry):
    """carry = one_tile(start, tile, live [tile, 1], carry) for each tile of
    the `rows` sorted rows that holds a row below `total`: ceil(total / tile)
    trips, a number known at run time only (a `while`, which has no automatic
    gradient: the callers are `custom_vjp` rules). `rows` need not be a
    multiple of the tile: the last trip then starts at rows - tile and
    writes some rows a second time, with the same values."""
    tile = min(ROW_TILE, rows)

    def trip(i, carry):
        start = jnp.minimum(i * tile, rows - tile)
        live = (start + jnp.arange(tile) < total)[:, None]
        return one_tile(start, tile, live, carry)

    return jax.lax.fori_loop(0, (total + tile - 1) // tile, trip, carry)


def _slot_sum(rows, rank, total, slots, gate=None):
    """rows [A, D] by sorted row -> [N, D] in their dtype: the float32 sum
    over a token's `slots` assignments of the row (times gate [slots, N],
    where given). The gather by `rank` lays the rows slot-major, which is
    [slots, N, D] as it stands, and the sum is accumulated slot by slot: no
    relayout, and no float32 copy of the buffer. An assignment that is not
    held is selected away, not multiplied by zero: its row may hold
    anything."""
    by_slot = rows[rank].reshape(slots, -1, rows.shape[1])
    held = None if total is None else (rank < total).reshape(slots, -1, 1)
    acc = 0.0
    for j in range(slots):
        # the select a slot, so that it fuses into the sum: over the whole
        # buffer it is a pass of its own (1.5 ms a layer, my chip run, PR 32)
        term = by_slot[j] if held is None else jnp.where(held[j], by_slot[j],
                                                         0)
        term = term.astype(jnp.float32)
        acc = acc + (term if gate is None else term * gate[j][:, None])
    # row-major, as the gather made the rows: where the consumer wants the
    # tokens minor (a [1, T, D] residual stream on the v5e), XLA otherwise
    # carries that layout back through the sum and transposes the [A, D]
    # buffer instead of the [N, D] result (AOT compile, PR 32)
    return with_layout_constraint(acc.astype(rows.dtype),
                                  Layout(major_to_minor=(0, 1)))


@jax.custom_vjp
def _dispatch(x, order, rank, total):
    """x [N, D]; order [A] the assignment (slot-major: a = slot * N + token,
    A = top_k * N) at each sorted row, rank [A] its inverse; `total` the
    sorted rows that are held (None, at trace time: all). -> [A, D], sorted
    row r is x[order[r] % N] below `total` and zero from there on."""
    token = order % x.shape[0]
    if total is None:
        return x[token]

    def one_tile(start, tile, live, rows):
        t = jax.lax.dynamic_slice(token, (start,), (tile,))
        return jax.lax.dynamic_update_slice(
            rows, jnp.where(live, x[t], 0), (start, 0))

    return _held_tiles(order.shape[0], total, one_tile,
                       jnp.zeros((order.shape[0], x.shape[1]), x.dtype))


def _dispatch_fwd(x, order, rank, total):
    return _dispatch(x, order, rank, total), (
        rank, total, order.shape[0] // x.shape[0])


def _dispatch_bwd(res, g):
    """A token's gradient is the sum of its held assignments' rows; a row
    past `total` holds whatever the matmuls' transposes left there."""
    rank, total, slots = res
    return _slot_sum(g, rank, total, slots), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(y, gate, order, rank, total):
    """y [A, D] the experts' outputs by sorted row, gate [top_k, N] float32
    the assignments' weights; order, rank and `total` as in `_dispatch`.
    -> [N, D] in y's dtype: the float32 sum over a token's slots of weight
    times output (`_slot_sum`)."""
    return _slot_sum(y, rank, total, gate.shape[0], gate)


def _combine_fwd(y, gate, order, rank, total):
    return _combine(y, gate, order, rank, total), (y, gate, order, rank,
                                                   total)


def _combine_bwd(res, g):
    """Both gradients on the experts' side, where the held rows are
    contiguous: sorted row r of dy is g[its token] times its weight, and its
    weight's gradient is the dot of g[its token] with y[r]; the weights'
    gradients then go back to slot-major by `rank`, [A] numbers. Only the
    tiles below `total` are gathered; dy is zero from `total` on."""
    y, gate, order, rank, total = res
    token, weight = order % gate.shape[1], gate.reshape(-1)[order]

    def both(g_rows, y_rows, w_rows):
        g_rows = g_rows.astype(jnp.float32)
        return ((g_rows * w_rows[:, None]).astype(y.dtype),
                jnp.sum(g_rows * y_rows.astype(jnp.float32), axis=-1))

    if total is None:
        dy, dweight = both(g[token], y, weight)
    else:
        def one_tile(start, tile, live, carry):
            t = jax.lax.dynamic_slice(token, (start,), (tile,))
            dy_rows, dw_rows = both(
                jnp.where(live, g[t], 0),
                jnp.where(live, jax.lax.dynamic_slice(
                    y, (start, 0), (tile, y.shape[1])), 0),
                jax.lax.dynamic_slice(weight, (start,), (tile,)))
            return (jax.lax.dynamic_update_slice(carry[0], dy_rows,
                                                 (start, 0)),
                    jax.lax.dynamic_update_slice(carry[1], dw_rows,
                                                 (start,)))

        dy, dweight = _held_tiles(
            order.shape[0], total, one_tile,
            (jnp.zeros_like(y), jnp.zeros(order.shape, jnp.float32)))
    return dy, dweight[rank].reshape(gate.shape), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _gated_silu(gate, up):
    """silu(gate) * up in float32, back in the inputs' dtype."""
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def _gated_relu(gate, up):
    """relu(gate) * up (ReGLU) in float32, back in the inputs' dtype."""
    return (jax.nn.relu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def _gated(gate, up, activation):
    if activation not in ("silu", "relu"):
        raise ValueError("routed_ffn activation must be 'silu' or 'relu', "
                         "got %r" % (activation,))
    return (_gated_silu if activation == "silu" else _gated_relu)(gate, up)


# what a sigmoid router's renormalisation adds to the chosen scores' sum
# (modeling_lfm2_moe.py; a softmax router's sum cannot vanish and adds nothing)
SIGMOID_NORM_EPS = 1e-6


def _route(logits, top_k, norm_topk_prob, scoring, expert_bias, scale):
    """(scores [N, E], the logits' logsumexp [N] or None under sigmoid, the
    chosen experts' weights [N, top_k], their indices) from float32 router
    logits [N, E]: `routed_ffn`'s choice, by its docstring."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError("routed_ffn scoring must be 'softmax' or 'sigmoid', "
                         "got %r" % (scoring,))
    if scoring == "softmax":
        lse = jax.nn.logsumexp(logits, axis=-1)
        probs = jnp.exp(logits - lse[:, None])
    else:
        lse, probs = None, jax.nn.sigmoid(logits)
    if expert_bias is None:
        gate, expert = jax.lax.top_k(probs, top_k)         # [N, top_k]
    else:
        _, expert = jax.lax.top_k(
            probs + expert_bias.astype(jnp.float32), top_k)
        gate = jnp.take_along_axis(probs, expert, axis=-1)
    if norm_topk_prob:
        total = gate.sum(-1, keepdims=True)
        if scoring == "sigmoid":
            total = total + SIGMOID_NORM_EPS
        gate = gate / total
    if scale != 1.0:
        gate = gate * scale
    return probs, lse, gate, expert


def routed_ffn(x, router, w_gate, w_up, w_down, top_k, norm_topk_prob=False,
               expert_dtype=None, router_x=None, activation="silu",
               first_expert=0, scoring="softmax", expert_bias=None,
               scale=1.0):
    """Dropless top-k routed gated experts over tokens x [N, D].

    router [D, E]; w_gate, w_up [H, D, F]; w_down [H, F, D]; no bias. The
    gated unit is act(x @ w_gate) * (x @ w_up) with `activation` "silu"
    (SwiGLU) or "relu" (ReGLU). The router reads `router_x` [N, D] where it
    is given (a model that routes from another tensor than the one the
    experts transform, so that a deployment can fetch experts early) and x
    itself where it is None.

    H is the experts held: the weights' leading dimension. With H = E every
    expert lives here. With H < E this is one chip's share of an
    expert-parallel layer: it holds experts first_expert .. first_expert +
    H - 1, routes every token over all E, computes the assignments that fall
    on its own experts, and returns that partial sum (a token none of whose
    choices is held gets zeros). The shares of all chips add up to the
    whole layer's output; nothing here stands in for the exchange.

    Every assignment to a held expert is computed, whatever the imbalance:
    there is no capacity. The assignments are numbered slot-major (a = slot
    * N + token) and sorted by expert, those to experts that are not held
    last, and the three expert matmuls run grouped over the sorted rows
    (`_grouped_matmul`) with the held experts' counts as group sizes, so
    they cost the held assignments' operations and not the stored experts'.
    The row buffer is top_k * N rows whatever H is, because every one of a
    token's choices may be held (1.5 N on average at 6 of 64 with 16 held).

    Four permutations move rows, a layer's forward and backward. The two
    that produce sorted rows (`_dispatch` forward, `_combine` backward)
    gather, where a share is held (`rows_moved`), only the tiles of
    `ROW_TILE` rows below total = sizes.sum(), in a loop whose trip count
    is known at run time: the share of the buffer they touch is
    ExpertLoad[first_expert : first_expert + H].sum() / (top_k * N), a
    value every caller can fetch. Sorted rows from `total` on are zero,
    in the rows and in the gradient that `_combine` hands the matmuls. The
    two that produce a token's rows (`_combine` forward, `_dispatch`
    backward) gather all top_k * N, a token's held slots being scattered,
    and sum over the leading axis of [top_k, N, D], which is the buffer as
    it lies: numbered token-major, top_k = 6 made each of them a relayout
    of the buffer (6 rows do not fill a tile of 8; PERF.md section 6, PR
    32).

    The rows past `total` belong to no group. What `ragged_dot` does with
    them depends on the backend: on the CPU it writes zeros there and its
    transpose gives them a zero gradient; on the v5e it neither reads nor
    writes them, so its time follows the groups' sum and not the buffer
    (0.75 ms for 12288 of 49152 rows of [2560] x [16, 2560, 768], 2.03 ms
    for all 49152), and they hold whatever the buffer held before, in the
    output and in the gradient of its left operand alike (my chip run, PR
    31). So nothing rests on them: `_slot_sum` selects (not multiplies)
    the held assignments' rows, forward and backward, so no NaN in an
    unwritten row reaches a token or its gradient.

    The router's matmul, softmax and top-k are float32 at full precision
    whatever x's dtype; the experts compute in `expert_dtype` (x's own if
    None; bfloat16 under AMP) and the top_k weighted outputs of a token are
    summed in float32.

    `scoring` "softmax" scores a token's experts by the softmax of its
    router logits, "sigmoid" by s = sigmoid(logits), each expert on its own.
    `expert_bias` [E], where given, enters the choice and nothing else: the
    top_k is over s + b and the weights are gathered from s, so the bias
    moves which experts run and never how much one counts, and it has no
    gradient (its only use is an argument of top_k; the router's gradient
    comes through s). With norm_topk_prob a sigmoid router divides by the
    chosen scores' sum + SIGMOID_NORM_EPS. `scale` multiplies the weights
    last. Under sigmoid scoring the balance and z terms are zeros: both are
    defined on a softmax's probabilities and its logsumexp.

    Returns (out [N, D] in the experts' dtype,
             load-balance term [1]: E * sum_e (c_e / N) * mean_n p[n, e],
             router z term [1]: mean_n logsumexp(logits[n])^2,
             c [E] int32: assignments an expert over ALL E, sum = top_k * N;
             c[first_expert : first_expert + H] are the rows computed).
    """
    n = x.shape[0]
    e, held = router.shape[1], w_gate.shape[0]
    if not 0 <= first_expert <= e - held:
        raise ValueError("routed_ffn holds experts %d..%d of %d"
                         % (first_expert, first_expert + held - 1, e))
    dtype = jnp.dtype(expert_dtype or x.dtype)
    logits = jnp.dot((x if router_x is None else router_x)
                     .astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs, lse, gate, expert = _route(logits, top_k, norm_topk_prob, scoring,
                                      expert_bias, scale)

    # assignment a = j * N + n (slot-major); `order` lists the assignments by
    # expert (stable, so by slot then token inside an expert), `rank` is
    # where each went
    expert, gate = expert.T.reshape(-1), gate.T
    load = jnp.sum(expert[:, None] == jnp.arange(e), axis=0, dtype=jnp.int32)
    if rows_moved(e, held) == "all":
        sort_key, sizes, total = expert, load, None
    else:
        local = expert - first_expert
        here = (local >= 0) & (local < held)
        sort_key = jnp.where(here, local, held)    # not held: past the groups
        sizes = load[first_expert:first_expert + held]
        total = sizes.sum()
    order = jnp.argsort(sort_key, stable=True)
    rank = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))

    rows = _dispatch(x.astype(dtype), order, rank, total)
    hidden = _gated(_grouped_matmul(rows, w_gate.astype(dtype), sizes),
                    _grouped_matmul(rows, w_up.astype(dtype), sizes),
                    activation)
    y = _grouped_matmul(hidden, w_down.astype(dtype), sizes)
    out = _combine(y, gate, order, rank, total)

    if scoring == "sigmoid":
        return out, jnp.zeros((1,), jnp.float32), \
            jnp.zeros((1,), jnp.float32), load
    balance = e * jnp.sum(load.astype(jnp.float32) / n * probs.mean(0))
    z = jnp.mean(jnp.square(lse))
    return out, balance.reshape(1), z.reshape(1), load
