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
token's slots are summed without a relayout; a share narrower than top_k
numbers its assignments by held expert instead (held * N: `numbered_by`).
Where a share is held, the
passes between the router and the layer's output touch the held rows only,
in loops whose trip count
is the held assignments' (`_held_experts`, `_combine`): the sorted rows are
gathered and the output's gradient weighted a tile of held rows at a time
(`_held_rows`, `_held_weighted`), a token's held rows are gathered and
summed by a one-hot matmul a tile of token-major places at a time
(`_token_sum`), and `_gated`'s transpose runs in the held tiles
(`_held_gated_transpose`); on the kernels' route `_gated` forward is the
gate/up kernel's epilogue and the buffers of sorted rows start as a call's
output that nothing filled (`_sorted_rows_start`).
"""
import functools

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
# The two grouped-matmul routes the expert matmuls of `routed_ffn` take, as
# the counter ptpu_moe_layers_total{path} names them: XLA's own, and the
# kernels of ops/expert_gmm.py.
GROUPED_MATMUL = "ragged_dot"
KERNEL_MATMUL = "expert_gmm"

# The widest [K, N] matrix the kernels hold whole in VMEM, in bytes: d
# weights keeps its two output buffers and a float32 accumulator of it (60 MiB
# at 12 MiB in bfloat16, of the 100 the kernels ask for). The cells' are 2 to
# 7 MiB.
_RESIDENT_BYTES = 12 << 20


def matmul_route(width, expert_width, dtype, mesh=None):
    """Who runs a layer's nine expert matmuls, decided from what the call
    can see, as embedding_grad.grad_form decides: the kernels where they are
    on (a TPU), the step is one device's (a Mosaic call does not partition),
    both widths are whole lane tiles and an expert's matrix fits the VMEM
    the kernels keep it in; else `jax.lax.ragged_dot`. At every group size
    the benchmark's cells have, 80 to 2048 rows, the kernels are the faster
    (chip_smoke.py --phases M), so the rows are no part of the rule."""
    from ..ops import kernel_config
    if mesh is not None or width % 128 or expert_width % 128 \
            or width * expert_width * jnp.dtype(dtype).itemsize \
            > _RESIDENT_BYTES or not kernel_config.pallas_on("gmm"):
        return GROUPED_MATMUL
    return KERNEL_MATMUL


def _grouped_matmul(lhs, rhs, group_sizes, plan=None):
    """lhs [M, K] rows sorted by group, rhs [G, K, N], group_sizes [G] with
    sum M: row m times its own group's matrix, [M, N] in lhs's dtype. Costs
    M x K x N multiply-adds whatever G is. `plan` is the kernels' list of
    visits (expert_gmm.plan of the same sizes), made once a layer; None:
    `ragged_dot`."""
    if plan is not None:
        return _kernel_matmul(lhs, rhs, plan)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)


@jax.custom_vjp
def _kernel_matmul(lhs, rhs, plan):
    from ..ops import expert_gmm
    return expert_gmm.gmm(lhs, rhs, plan)


def _kernel_matmul_fwd(lhs, rhs, plan):
    return _kernel_matmul(lhs, rhs, plan), (lhs, rhs, plan)


def _kernel_matmul_bwd(res, d_out):
    lhs, rhs, plan = res
    return _matmul_transposes(lhs, rhs, None, plan, d_out) + (None,)


_kernel_matmul.defvjp(_kernel_matmul_fwd, _kernel_matmul_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _kernel_unit(rows, w_gate, w_up, plan, unit):
    """Every expert held, on the kernels: unit(rows x w_gate, rows x w_up)
    (unit(rows x w_up) with `w_gate` None) by sorted row, one kernel that
    reads a row tile once and makes the unit its epilogue
    (expert_gmm.gmm_unit). The rule keeps the kernel's `gate` and `up` and
    its backward pass is jax's own of the unit and the matmuls'
    transposes."""
    return _kernel_unit_fwd(rows, w_gate, w_up, plan, unit)[0]


def _kernel_unit_fwd(rows, w_gate, w_up, plan, unit):
    from ..ops import expert_gmm
    *made, hidden = expert_gmm.gmm_unit(rows, w_gate, w_up, plan, unit)
    return hidden, (rows, w_gate, w_up, plan, made)


def _kernel_unit_bwd(unit, res, d_hidden):
    rows, w_gate, w_up, plan, made = res
    d_made = jax.vjp(unit, *made)[1](d_hidden)
    d_rows, d_w_up = _matmul_transposes(rows, w_up, None, plan, d_made[-1])
    if w_gate is None:
        return d_rows, None, d_w_up, None
    d_rows_gate, d_w_gate = _matmul_transposes(rows, w_gate, None, plan,
                                               d_made[0])
    return d_rows_gate + d_rows, d_w_gate, d_w_up, None


_kernel_unit.defvjp(_kernel_unit_fwd, _kernel_unit_bwd)


def rows_moved(experts, held):
    """Which rows of the sorted row buffer `routed_ffn`'s passes touch,
    decided from the shapes: "all" where every expert is held (every row is
    in a group: four gathers of the buffer and the elementwise passes XLA
    makes of the rest), "held" where a share is: the four permutations, the
    sum of the two d rows and `_gated`'s transpose all run in the tiles
    below the held assignments' count, a run-time value (on the kernels'
    route `_gated` forward too, and no buffer is filled past them)."""
    return "all" if held == experts else "held"


def numbered_by(experts, held, top_k):
    """Which axis numbers the assignments of a held share, decided from the
    shapes: a token's choices are distinct experts, so it has an assignment
    a top-k slot (top_k x N of them) and at most one a held expert (held x
    N), and every integer pass between the router and the rows (the sort
    key, `order`, `rank`, `_token_places`, the windows of `_token_sum`, the
    weights' gradient) runs over whichever is numbered. "expert" where the
    share is narrower than top_k (a = h * N + token, h the held expert:
    Nemotron-3-Super's 8 of 512 at top-22), "slot" where it is not (a = slot
    * N + token, the shorter there), None where every expert is held
    (`rows_moved` "all": every slot is a row, and slot-major is the buffer
    as it lies)."""
    if held == experts:
        return None
    return "expert" if held < top_k else "slot"


# Rows a trip of the held-rows loops moves; flat from 512 to 2048 (my chip run,
# PR 32). One SmallThinker layer forward and backward, 12426 of 49152 rows held,
# ms: 128 20.95, 256 20.60, 512 20.38, 1024 20.56, 2048 20.60, 4096 +0.7, 8192
# +1.2. The cell, tokens/s/chip over two seeds: 512 44,211, 1024 44,261, 2048
# 44,269, 4096 44,213. Half a tile is gathered for nothing; a trip costs little.
ROW_TILE = 1024

# Places a trip of the token-side sum handles (`_token_sum`): its gather's rows
# and the side of its one-hot matmul, whose operations grow with the square.
# One sum alone, weighted / plain, ms (my chip runs, PR 40), with the slots
# taken one by one: at SmallThinker's sizes (13,557 places of 49,152) 256 1.47 /
# 1.24, 512 1.66 / 1.19; Qwen3-Next's (4,664 of 40,960) 256 0.53 / 0.43, 512
# 0.61 / 0.42; LFM2's (10,654 of 32,768) 256 0.95 / 0.80, 512 1.02 / 0.73; 128
# and 384 were slower in an earlier form. A layer forward and backward moved by
# under 0.1 ms from 128 to 512. With the slots taken together, at 256: 1.44 /
# 1.21, 0.48 / 0.41, 0.93 / 0.79.
SUM_TILE = 256


# The four loops over the held rows (`_held_rows`, `_token_sum`,
# `_held_weighted`, `_held_gated_transpose`) are jitted with their tile static:
# a model's expert layers are alike, so a step is traced and lowered with one
# copy of each loop and a call a layer, which XLA inlines. Traced anew in every
# layer the SmallThinker cell's warm set-up read 18.4 s against the parent's
# 16.2, so 17.1 (my chip runs, PR 40). What a loop reads of the module it is
# handed as an argument: a trace is kept under its arguments.
def _held_tiles(rows, total, one_tile, carry, tile):
    """carry = one_tile(start, tile, live [tile, 1], carry) for each tile
    (`ROW_TILE` rows) of the `rows` sorted rows that holds a row below
    `total`: ceil(total / tile) trips, a number known at run time only (a
    `while`, which has no automatic gradient: the callers are `custom_vjp`
    rules). `rows` need not be a multiple of the tile: the last trip then
    starts at rows - tile and meets some rows a second time
    (`_first_time`)."""
    tile = min(tile, rows)

    def trip(i, carry):
        start = jnp.minimum(i * tile, rows - tile)
        live = (start + jnp.arange(tile) < total)[:, None]
        return one_tile(start, tile, live, carry)

    return jax.lax.fori_loop(0, (total + tile - 1) // tile, trip, carry)


def _met(buffer, start, tile):
    """The tile at `start` of a buffer that the trip goes on to write over,
    read once: behind a barrier, so that XLA does not fuse the slice into
    several consumers that each read the buffer, which it then copies whole
    in every trip before the update (AOT compile of the Qwen3-Next cell's
    step, PR 40)."""
    return jax.lax.optimization_barrier(jax.lax.dynamic_slice(
        buffer, (start, 0), (tile, buffer.shape[1])))


def _first_time(start, tile):
    """[tile, 1]: the rows of the tile at `start` that no earlier trip of
    `_held_tiles` met. All of them, but in a last trip moved back to end
    with the buffer. A trip that writes what it computed from the carry
    itself keeps the others as they are."""
    return (start + jnp.arange(tile) >= -(-start // tile) * tile)[:, None]


def _slot_sum(rows, rank, slots, gate=None):
    """Every expert held. rows [A, D] by sorted row -> [N, D] in their dtype:
    the float32 sum over a token's `slots` assignments of the row (times gate
    [slots, N], where given). The gather by `rank` lays the rows slot-major,
    which is [slots, N, D] as it stands, and the sum is accumulated slot by
    slot: no relayout, and no float32 copy of the buffer."""
    by_slot = rows[rank].reshape(slots, -1, rows.shape[1])
    acc = 0.0
    for j in range(slots):
        term = by_slot[j].astype(jnp.float32)
        acc = acc + (term if gate is None else term * gate[j][:, None])
    return _row_major(acc.astype(rows.dtype))


def _row_major(out):
    """[N, D] row-major, as the passes over the rows made it: where the
    consumer wants the tokens minor (a [1, T, D] residual stream on the
    v5e), XLA otherwise carries that layout back through the sum and
    transposes the [A, D] buffer instead of the [N, D] result (AOT compile,
    PR 32)."""
    return with_layout_constraint(out, Layout(major_to_minor=(0, 1)))


def _token_places(rank, total, slots):
    """Where a share is held: the held assignments numbered token-major (a
    token's held slots adjacent in slot order, tokens ascending), on [N]
    integers only: no sort, no row moves. A token with no held assignment
    takes one place all the same (it reads nothing), so that p places never
    span more than p tokens and miss none.

    rank [A] by slot-major assignment, `total` the held ones (rank < total)
    -> (held [slots, N] bool, begin [N] the first place of each token, count
    the places: total + the tokens without)."""
    held = (rank < total).reshape(slots, -1)
    taken = jnp.maximum(jnp.sum(held, axis=0, dtype=jnp.int32), 1)
    end = jnp.cumsum(taken)
    return held, end - taken, end[-1]


def _bf16_terms(w):
    """float32 w as three bfloat16 terms whose sum is w (24 bits of
    mantissa in three times 8): a bfloat16 product with each is exact in
    float32."""
    terms = []
    for _ in range(3):
        terms.append(w.astype(jnp.bfloat16))
        w = w - terms[-1].astype(jnp.float32)
    return terms


@functools.partial(jax.jit, static_argnames=("tile",))
def _token_sum(parts, rank, places, gate=None, *, tile):
    """A share held. parts: arrays [A, D] by sorted row whose sum, formed in
    their dtype as `add_any` would, is the row (the two that the transposes
    of `w_gate`'s and `w_up`'s matmuls return, added where they are read and
    not in a pass of their own) -> [N, D] in that dtype: the float32 sum
    over a token's HELD assignments of the row (times gate [slots, N]
    float32, where given), touching the held rows only: ceil(count /
    SUM_TILE) trips over the places of `_token_places`.

    A trip takes SUM_TILE places, which are slots of at most SUM_TILE
    consecutive tokens starting at the token its first place belongs to. It
    works out on that window of [slots, SUM_TILE] integers which slot each
    place is, gathers the places' rows, and sums them by token with one
    matmul: the left operand [tokens, places] has a place's weight in its
    token's row and zeros elsewhere, the accumulation is float32, and the
    [SUM_TILE, D] result is written at the window's first row (the rows
    past the trip's last token are zeros that later trips write over). The
    product of a float32 weight and a row is formed in float32 and never
    rounded before the sum: bfloat16 rows meet the weight as three bfloat16
    terms (three blocks of the left operand, exact products, the three
    results added), float32 rows a float32 operand at full precision. A
    token whose places straddle two trips is carried in float32 from one
    to the next, which writes its row again, whole. No row from `total` on
    is read."""
    held, begin, count = places
    slots, n = held.shape
    d, dtype = parts[0].shape[1], parts[0].dtype
    exact = dtype == jnp.bfloat16
    tile = min(tile, slots * n)
    # the token each trip starts in (the last one whose first place is not
    # past the trip's), and one more for the trip after the last
    starts = jnp.arange(-(-slots * n // tile) + 1, dtype=jnp.int32) * tile
    first_of = jnp.sum(begin <= starts[:, None], axis=1, dtype=jnp.int32) - 1
    # windows of `tile` tokens are sliced anywhere up to the last token
    held = jnp.pad(held, ((0, 0), (0, tile)))
    sorted_row = jnp.pad(rank.reshape(slots, n).astype(jnp.int32),
                         ((0, 0), (0, tile)))
    begin = jnp.pad(begin, (0, tile))
    weight = None if gate is None else jnp.pad(gate, ((0, 0), (0, tile)))
    place = jnp.arange(tile)[:, None]

    def trip(i, carry):
        out, partial = carry
        start, first = i * tile, first_of[i]

        def window(v):
            return jax.lax.dynamic_slice(v, (0, first), (slots, tile))

        here, at_row = window(held), window(sorted_row)
        b = jax.lax.dynamic_slice(begin, (first,), (tile,))
        # a token's held slots take its places in slot order; one without
        # takes one place, which no slot is
        taken = here.at[0].set(here[0] | ~here.any(0)).astype(jnp.int32)
        at = b - start + jnp.cumsum(taken, axis=0) - taken   # [slots, tokens]
        # [slots, places, tokens], the tokens in the lanes as the windows
        # have them: a place is at most one slot of one token
        is_place = (at[:, None] == place) & here[:, None]
        read = jnp.sum(jnp.where(is_place, at_row[:, None] + 1, 0),
                       axis=(0, 2)) - 1
        lhs = jnp.sum(
            jnp.where(is_place, 1 if gate is None else window(weight)[:, None],
                      0), axis=0, dtype=dtype if gate is None else jnp.float32)
        part = jnp.where((read >= 0)[:, None],
                         sum(p[jnp.maximum(read, 0)] for p in parts), 0)
        blocks = _bf16_terms(lhs) if exact and gate is not None else [lhs]
        sums = jax.lax.dot_general(
            jnp.concatenate(blocks, axis=1), part, (((0,), (0,)), ((), ())),
            precision=None if exact else jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        sums = sum(sums[k * tile:(k + 1) * tile] for k in range(len(blocks)))
        sums = sums.at[0].add(jnp.where(b[0] < start, partial, 0))
        carried = jnp.clip(first_of[i + 1] - first, 0, tile - 1)
        return (jax.lax.dynamic_update_slice(out, sums.astype(dtype),
                                             (first, 0)),
                jax.lax.dynamic_index_in_dim(sums, carried, 0,
                                             keepdims=False))

    out, _ = jax.lax.fori_loop(
        0, (count + tile - 1) // tile, trip,
        (jnp.zeros((n + tile, d), dtype), jnp.zeros((d,), jnp.float32)))
    return _row_major(out[:n])


@jax.custom_vjp
def _dispatch(x, order, rank):
    """Every expert held. x [N, D]; order [A] the assignment (slot-major: a
    = slot * N + token, A = top_k * N) at each sorted row, rank [A] its
    inverse. -> [A, D], sorted row r is x[order[r] % N]."""
    return x[order % x.shape[0]]


def _dispatch_fwd(x, order, rank):
    return _dispatch(x, order, rank), (rank, order.shape[0] // x.shape[0])


def _dispatch_bwd(res, g):
    """A token's gradient is the sum of its assignments' rows."""
    rank, slots = res
    return _slot_sum(g, rank, slots), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _sorted_rows_start(shape, dtype, like, plan):
    """What a loop over the held tiles starts a buffer of sorted rows [A, .]
    from, which it writes below `total` and nothing reads from there on: on
    the kernels' route (`plan` given) a Mosaic call's output that nothing
    wrote (expert_gmm.unwritten; `like` an array at hand that the forward op
    and a grad op's replay compute alike), so that no pass fills A rows for
    the sake of the dead ones; zeros on `ragged_dot`'s, as ever."""
    if plan is None:
        return jnp.zeros(shape, dtype)
    from ..ops import expert_gmm
    return expert_gmm.unwritten(shape, dtype, like)


@functools.partial(jax.jit, static_argnames=("tile",))
def _held_rows(x, order, total, rows, *, tile):
    """A share held. x [N, D], order as in `_dispatch`, `total` the sorted
    rows that are held, `rows` [A, D] the buffer to write them into
    (`_sorted_rows_start`). -> [A, D], sorted row r is x[order[r] % N]
    below `total`, zero in the rest of the last tile that holds one, and
    from there on what `rows` held: only the tiles below `total` are
    gathered."""
    token = order % x.shape[0]

    def one_tile(start, tile, live, rows):
        t = jax.lax.dynamic_slice(token, (start,), (tile,))
        return jax.lax.dynamic_update_slice(
            rows, jnp.where(live, x[t], 0), (start, 0))

    return _held_tiles(order.shape[0], total, one_tile, rows, tile)


@jax.custom_vjp
def _combine(y, gate, order, rank, total, places):
    """y [A, D] the experts' outputs by sorted row, gate [slots, N] float32
    the assignments' weights (slots: top_k, or the held experts where the
    assignments are numbered by them); order and rank as in `_dispatch`;
    `total` the sorted rows that are held and `places` their token-major
    numbering (`_token_places`), both None where every expert is held. ->
    [N, D] in y's dtype: the float32 sum over a token's slots of weight
    times output (`_slot_sum`; over its held slots, `_token_sum`)."""
    if total is None:
        return _slot_sum(y, rank, gate.shape[0], gate)
    return _token_sum((y,), rank, places, gate, tile=SUM_TILE)


def _combine_fwd(y, gate, order, rank, total, places):
    return _combine(y, gate, order, rank, total, places), (
        y, gate, order, rank, total)


def _weighted(g_rows, y_rows, w_rows):
    """(d y, d weight) of rows of weight * y summed into a token whose
    gradient the rows of g hold: in float32, d y back in y's dtype."""
    g_rows = g_rows.astype(jnp.float32)
    return ((g_rows * w_rows[:, None]).astype(y_rows.dtype),
            jnp.sum(g_rows * y_rows.astype(jnp.float32), axis=-1))


@functools.partial(jax.jit, static_argnames=("tile",))
def _held_weighted(y, g, token, weight, total, *, tile):
    """`_weighted` of g[token] in the tiles below `total`, d y written over
    y, tile by tile: from `total` on it holds what y held, which no group
    reads, and d weight [A] zeros."""
    def one_tile(start, tile, live, carry):
        y_then_dy, dweight = carry
        t = jax.lax.dynamic_slice(token, (start,), (tile,))
        met = _met(y_then_dy, start, tile)
        dy_rows, dw_rows = _weighted(
            jnp.where(live, g[t], 0), jnp.where(live, met, 0),
            jax.lax.dynamic_slice(weight, (start,), (tile,)))
        first = _first_time(start, tile)
        return (jax.lax.dynamic_update_slice(
                    y_then_dy, jnp.where(first, dy_rows, met), (start, 0)),
                jax.lax.dynamic_update_slice(
                    dweight, jnp.where(
                        first[:, 0], dw_rows, jax.lax.dynamic_slice(
                            dweight, (start,), (tile,))), (start,)))

    return _held_tiles(token.shape[0], total, one_tile,
                       (y, jnp.zeros(token.shape, jnp.float32)), tile)


def _combine_bwd(res, g):
    """Both gradients on the experts' side, where the held rows are
    contiguous: sorted row r of dy is g[its token] times its weight, and its
    weight's gradient is the dot of g[its token] with y[r]. The weights
    come to their sorted rows, gate.reshape(-1)[order], by a sort keyed on
    `rank`, and their gradients go back to the assignments' numbering,
    dweight[rank], by one keyed on `order` (`_sorted_by`: [A] scalars
    each, which XLA's gather moves at 8 ns an index), under every
    numbering. Where a share is held only the tiles below `total` are
    gathered (`_held_weighted`)."""
    y, gate, order, rank, total = res
    token = order % gate.shape[1]
    weight, = _sorted_by(rank, gate.reshape(-1))
    if total is None:
        dy, dweight = _weighted(g[token], y, weight)
    else:
        dy, dweight = _held_weighted(y, g, token, weight, total,
                                     tile=ROW_TILE)
    dweight, = _sorted_by(order, dweight)
    return dy, dweight.reshape(gate.shape), None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _gated_silu(gate, up):
    """silu(gate) * up in float32, back in the inputs' dtype."""
    return (jax.nn.silu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def _gated_relu(gate, up):
    """relu(gate) * up (ReGLU) in float32, back in the inputs' dtype."""
    return (jax.nn.relu(gate.astype(jnp.float32))
            * up.astype(jnp.float32)).astype(gate.dtype)


def _gated_unit(activation):
    """The gated unit of `activation`, looked up in the module when called."""
    if activation not in ("silu", "relu"):
        raise ValueError("routed_ffn activation must be 'silu' or 'relu', "
                         "got %r" % (activation,))
    return _gated_silu if activation == "silu" else _gated_relu


def _gated(gate, up, activation):
    return _gated_unit(activation)(gate, up)


def _ungated_relu2(up):
    """relu(up)^2 in float32, back in the input's dtype: the unit of an
    expert of two matrices, which has no gate branch."""
    r = jax.nn.relu(up.astype(jnp.float32))
    return (r * r).astype(up.dtype)


def _ungated_unit(activation):
    """The unit of an ungated expert (`w_gate` None), act(x @ w_up), looked
    up in the module when called."""
    if activation != "relu2":
        raise ValueError("routed_ffn without w_gate takes activation "
                         "'relu2', got %r" % (activation,))
    return _ungated_relu2


def _unit(activation, gated):
    """The experts' unit: gated, of (gate, up); of `up` alone where an
    expert is two matrices."""
    return _gated_unit(activation) if gated else _ungated_unit(activation)


def _matmul_transposes(lhs, rhs, sizes, plan, d_out):
    """(d lhs, d rhs) of `_grouped_matmul(lhs, rhs, sizes, plan)`."""
    if plan is not None:
        from ..ops import expert_gmm
        return (expert_gmm.gmm_drows(d_out, rhs, plan),
                expert_gmm.gmm_dweights(lhs, d_out, plan, rhs.dtype))
    return jax.vjp(lambda a, b: _grouped_matmul(a, b, sizes), lhs, rhs)[1](
        d_out)


@functools.partial(jax.jit, static_argnames=("unit", "tile"))
def _held_gated_transpose(gate, up, d_hidden, d_up, total, *, unit, tile):
    """(d gate, d up) of unit(gate, up) in the tiles below `total`: d gate
    written over d hidden, tile by tile (from `total` on it holds what d
    hidden held), d up over the buffer `d_up` (`_sorted_rows_start`), which
    keeps what it held there."""
    def one_tile(start, tile, live, carry):
        d_hidden_then_gate, d_up = carry

        def cut(v):
            return jax.lax.dynamic_slice(v, (start, 0), (tile, v.shape[1]))

        met = _met(d_hidden_then_gate, start, tile)
        d_gate_rows, d_up_rows = jax.vjp(unit, cut(gate), cut(up))[1](met)
        first = _first_time(start, tile)
        return (jax.lax.dynamic_update_slice(
                    d_hidden_then_gate, jnp.where(first, d_gate_rows, met),
                    (start, 0)),
                jax.lax.dynamic_update_slice(
                    d_up, jnp.where(first, d_up_rows, cut(d_up)), (start, 0)))

    return _held_tiles(gate.shape[0], total, one_tile, (d_hidden, d_up),
                       tile)


@functools.partial(jax.jit, static_argnames=("unit", "tile"))
def _held_ungated_transpose(up, d_hidden, hidden, total, *, unit, tile):
    """d up of unit(up) in the tiles below `total`, written over d hidden,
    tile by tile (from `total` on it holds what d hidden held). `hidden`,
    where given, is a buffer [A, F] (`_sorted_rows_start`) into which the
    same trips write unit(up), the hidden rows again: -> (d up, hidden),
    hidden None where none was given."""
    def one_tile(start, tile, live, carry):
        d_hidden_then_up, hidden = carry
        met = _met(d_hidden_then_up, start, tile)
        hidden_rows, transpose = jax.vjp(unit, jax.lax.dynamic_slice(
            up, (start, 0), (tile, up.shape[1])))
        d_up_rows, = transpose(met)
        if hidden is not None:
            # a last trip moved back writes what an earlier one wrote
            hidden = jax.lax.dynamic_update_slice(hidden, hidden_rows,
                                                  (start, 0))
        return (jax.lax.dynamic_update_slice(
            d_hidden_then_up,
            jnp.where(_first_time(start, tile), d_up_rows, met), (start, 0)),
            hidden)

    return _held_tiles(up.shape[0], total, one_tile, (d_hidden, hidden), tile)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10,))
def _held_experts(x, w_gate, w_up, w_down, order, rank, sizes, plan, total,
                  places, activation):
    """A share held: x [N, D] -> the held experts' outputs by sorted row
    [A, D], through `_held_rows`, the three grouped matmuls and `_gated`; the
    integers as in `_combine`, `plan` as in `_grouped_matmul`. One rule, so
    that its backward pass can run the passes between the matmuls'
    transposes in the held tiles. On the kernels' route (`plan` given) the
    two matmuls into the unit and the unit are ONE kernel
    (expert_gmm.gmm_unit), the sorted rows are written into a buffer that
    nothing filled (`_sorted_rows_start`), and so no pass, forward or
    backward, touches a tile past `total`. `w_gate` None: experts of two
    matrices, act(rows @ w_up) @ w_down; of the forward pass the rule then
    keeps the rows and `up` alone, and its backward pass makes the hidden
    rows again (where keeping them is a buffer a layer: +0.28 GiB at the
    Nemotron cell's peak, AOT compile, PR 65): one pass over all the rows
    on `ragged_dot`, in the trips of the unit's transpose on the kernels
    (`_held_ungated_transpose`)."""
    return _held_experts_fwd(x, w_gate, w_up, w_down, order, rank, sizes,
                             plan, total, places, activation)[0]


def _held_experts_fwd(x, w_gate, w_up, w_down, order, rank, sizes, plan,
                      total, places, activation):
    rows = _held_rows(x, order, total, _sorted_rows_start(
        (order.shape[0], x.shape[1]), x.dtype, order, plan), tile=ROW_TILE)
    unit = _unit(activation, w_gate is not None)
    if plan is not None:
        from ..ops import expert_gmm
        *made, hidden = expert_gmm.gmm_unit(rows, w_gate, w_up, plan, unit)
    else:
        made = [_grouped_matmul(rows, w, sizes, plan)
                for w in (w_gate, w_up) if w is not None]
        hidden = unit(*made)
    gate, up = made if w_gate is not None else (None, made[0])
    return _grouped_matmul(hidden, w_down, sizes, plan), (
        rows, gate, up, None if w_gate is None else hidden, w_gate, w_up,
        w_down, rank, sizes, plan, total, places)


def _held_experts_bwd(activation, res, dy):
    """The matmuls' transposes as `_grouped_matmul`'s own; `_gated`'s in the
    tiles below `total` (`_held_gated_transpose`); and a token's gradient
    summed from the two matmuls' d rows where they lie (`_token_sum`): a row
    past `total` holds whatever the transposes left there."""
    rows, gate, up, hidden, w_gate, w_up, w_down, rank, sizes, plan, total, \
        places = res
    if w_gate is None:
        unit = _unit(activation, False)
        if plan is None:
            d_hidden, d_down = _matmul_transposes(unit(up), w_down, sizes,
                                                  plan, dy)
            d_up, _ = _held_ungated_transpose(up, d_hidden, None, total,
                                              unit=unit, tile=ROW_TILE)
        else:
            from ..ops import expert_gmm
            d_hidden = expert_gmm.gmm_drows(dy, w_down, plan)
            d_up, hidden = _held_ungated_transpose(
                up, d_hidden, _sorted_rows_start(up.shape, up.dtype, dy,
                                                 plan),
                total, unit=unit, tile=ROW_TILE)
            d_down = expert_gmm.gmm_dweights(hidden, dy, plan, w_down.dtype)
        d_rows, d_w_up = _matmul_transposes(rows, w_up, sizes, plan, d_up)
        return (_token_sum((d_rows,), rank, places, tile=SUM_TILE), None,
                d_w_up, d_down) + (None,) * 6
    d_hidden, d_down = _matmul_transposes(hidden, w_down, sizes, plan, dy)
    d_gate, d_up = _held_gated_transpose(
        gate, up, d_hidden, _sorted_rows_start(up.shape, up.dtype, d_hidden,
                                               plan),
        total, unit=_unit(activation, True), tile=ROW_TILE)
    d_rows_gate, d_w_gate = _matmul_transposes(rows, w_gate, sizes, plan,
                                               d_gate)
    d_rows_up, d_w_up = _matmul_transposes(rows, w_up, sizes, plan, d_up)
    return (_token_sum((d_rows_gate, d_rows_up), rank, places, tile=SUM_TILE),
            d_w_gate, d_w_up, d_down) + (None,) * 6


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _sorted_by(keys, *values):
    """`values` [A] in the order that makes `keys` ascend, one sort of
    tuples. With keys a permutation of 0 .. A - 1 that is v[inverse of
    keys], the gather, and v scattered to keys, both at once: every scalar
    that `routed_ffn` permutes goes this way (`rank`, the inverse of
    `order`: arange sorted by `order`; the weights by sorted row and their
    gradients back, `_combine_bwd`; `order` out of `rank` numbered by held
    expert), because XLA's gather and scatter of 32-bit scalars cost the
    v5e 8 and 5 ns an index (263 and 155 us at 32,768, 676 us for 90,112 of
    them filled) and a sort of 32,768 triples 25 us (my chip run, PR 62).
    What is read at an index a token chose is a compare (`_chosen`)."""
    return jax.lax.sort((keys,) + values, num_keys=1)[1:]


def _by_held_expert(local, gate, sizes):
    """The assignments of a share narrower than top_k numbered by held
    expert (`numbered_by`), a = h * N + token. local [top_k, N] a token's
    choices less first_expert, gate [top_k, N] float32 their weights, sizes
    [held] the held experts' counts -> (weight [held, N] float32: the weight
    of the token's choice of held expert h, 0 where it made none; order [A]
    the assignment at each sorted row, the held ones first, by expert then
    token, the rest behind them as they are numbered; rank [A] its inverse).

    A token's choices are distinct, so (h, token) is one assignment at most
    and one compare over [held, top_k, N] (the tokens in the lanes, as
    `local` lies) finds it and its weight; plain jax.numpy, which jax
    transposes, so a weight's gradient goes back to its slot. `rank` needs
    no sort: a held assignment's is the held ones before it in (h, token)
    order, a running sum along each expert's row behind the experts before
    it, and one that is not held follows them in its own order. `order`
    is the numbering sorted by that rank."""
    held, n = sizes.shape[0], local.shape[1]
    chosen = local[None] == jnp.arange(held)[:, None, None]
    member = chosen.any(1)
    weight = jnp.sum(jnp.where(chosen, gate[None], 0.0), axis=1)
    ends = jnp.cumsum(sizes)
    before = (ends - sizes)[:, None] + jnp.cumsum(member, axis=1,
                                                  dtype=jnp.int32)
    a = jnp.arange(held * n, dtype=jnp.int32).reshape(held, n)
    rank = jnp.where(member, before - 1, ends[-1] + a - before).reshape(-1)
    # `order` is the integers' alone, so the forward op's and the one a grad
    # op's replay of the rule makes are one to XLA: a sort that carried the
    # weights too took the rows' loops, a matmul and the unit with it into
    # the grad op while the weights came by a gather XLA did not merge (+4.5
    # ms a step in the Nemotron cell; my chip run, PR 62). The weights go to
    # their rows where they are read, in `_combine_bwd`
    order, = _sorted_by(rank, a.reshape(-1))
    return weight, order, rank


def _chosen(probs, expert):
    """probs [N, E] float32 at the experts a token chose, expert [N, top_k]
    distinct in a row -> [N, top_k], which is take_along_axis(probs, expert,
    -1) to the bit: a compare of `expert` against the experts' axis and a
    sum over it that has one term that is not zero, one reduce fusion over
    [top_k, E, N] with the tokens in the lanes that writes [top_k, N]. jax
    transposes it to where(chosen, d, 0) summed over the slots, so the
    gradient into the scores is a pass of the same shape and no scatter-add
    of top_k * N scalars into zeros. At Nemotron-3-Super's [22, 512, 4096]
    the two passes are 33 and 98 us a layer on the v5e where the gather was
    0.93 ms, 0.93 again in the grad op (the one operation of the replayed
    rule XLA did not merge with the forward op's) and the scatter-add 0.87
    (my chip runs, PRs 62 and 63).

    The result stands behind a barrier: a renormalisation sums it over the
    slots, and XLA merges a sum of a sum into ONE over [top_k, E] (float32
    additions in another order, so a weight an ulp off the gathered form)
    and then runs the compare a second time for the weights themselves
    (CPU compile, PR 63)."""
    chosen = expert.T[:, None] == jnp.arange(
        probs.shape[1], dtype=expert.dtype)[:, None]
    return jax.lax.optimization_barrier(
        jnp.sum(jnp.where(chosen, probs.T, 0.0), axis=1)).T


# what a sigmoid router's renormalisation adds to the chosen scores' sum
# (modeling_lfm2_moe.py; a softmax router's sum cannot vanish and adds nothing)
SIGMOID_NORM_EPS = 1e-6


def _group_limited(choice, groups):
    """`choice` [N, E] (the scores the top k is taken over) with every
    expert outside a token's kept groups at -inf: DeepSeek-V3's
    group-limited choice (`noaux_tc`, arXiv:2412.19437). groups = (n_group,
    topk_group): the E experts are n_group runs of E / n_group neighbours; a
    group's score is the sum of its two largest entries; the topk_group
    best groups stay (the lower index where two are level, as lax.top_k
    has it). The two largest by two max passes, the second over the row
    with the FIRST largest taken out, so two level entries count twice as
    a sort's top 2 would; kept is a compare of the chosen groups against
    the groups' axis: no gather, no scatter (`_chosen` says why)."""
    n_group, topk_group = groups
    n, e = choice.shape
    by_group = choice.reshape(n, n_group, e // n_group)
    first = jnp.argmax(by_group, axis=-1)
    within = jax.lax.broadcasted_iota(jnp.int32, by_group.shape, 2)
    second = jnp.where(within == first[..., None], -jnp.inf, by_group)
    score = by_group.max(-1) + second.max(-1)               # [N, n_group]
    _, best = jax.lax.top_k(score, topk_group)
    kept = (best[:, :, None] == jnp.arange(n_group)).any(1)
    return jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(n, e)


def _route(logits, top_k, norm_topk_prob, scoring, expert_bias, scale,
           norm_eps=None, groups=None):
    """(scores [N, E], the logits' logsumexp [N] or None under sigmoid, the
    chosen experts' weights [N, top_k], their indices) from float32 router
    logits [N, E]: `routed_ffn`'s choice, by its docstring. `groups`
    (n_group, topk_group), where given, limits the choice to a token's best
    groups (`_group_limited`). What that does to a SHARE of the experts: the
    held experts are neighbours (first_expert .. first_expert + H - 1), so
    they lie in one group or a few (Ling-3.0-flash's 0-7 of 512 in group 0
    of 8), and a token whose kept groups exclude theirs has no assignment
    here at all, whatever its scores for the held experts are: it gets
    zeros from this chip, as a token none of whose top k is held does."""
    if scoring not in ("softmax", "sigmoid"):
        raise ValueError("routed_ffn scoring must be 'softmax' or 'sigmoid', "
                         "got %r" % (scoring,))
    if scoring == "softmax":
        lse = jax.nn.logsumexp(logits, axis=-1)
        probs = jnp.exp(logits - lse[:, None])
    else:
        lse, probs = None, jax.nn.sigmoid(logits)
    # the bias is an argument of the choice and nothing else
    choice = probs if expert_bias is None \
        else probs + expert_bias.astype(jnp.float32)
    if groups is not None:
        choice = _group_limited(choice, groups)
    _, expert = jax.lax.top_k(choice, top_k)                   # [N, top_k]
    gate = _chosen(probs, expert)
    if norm_topk_prob:
        total = gate.sum(-1, keepdims=True)
        if scoring == "sigmoid":
            total = total + (SIGMOID_NORM_EPS if norm_eps is None
                             else norm_eps)
        gate = gate / total
    if scale != 1.0:
        gate = gate * scale
    return probs, lse, gate, expert


def routed_ffn(x, router, w_gate, w_up, w_down, top_k, norm_topk_prob=False,
               expert_dtype=None, router_x=None, activation="silu",
               first_expert=0, scoring="softmax", expert_bias=None,
               scale=1.0, norm_eps=None, mesh=None, groups=None):
    """Dropless top-k routed gated experts over tokens x [N, D].

    router [D, E]; w_gate, w_up [H, D, F]; w_down [H, F, D]; no bias. The
    gated unit is act(x @ w_gate) * (x @ w_up) with `activation` "silu"
    (SwiGLU) or "relu" (ReGLU). `w_gate` None: experts of two matrices,
    act(x @ w_up) @ w_down with `activation` "relu2", relu(.)^2; whatever
    this docstring says of the three matmuls and of `_gated` then holds of
    the two and of the unit between them. The router reads `router_x` [N,
    Dr] where it is given (a model that routes from another tensor than the
    one the experts transform, so that a deployment can fetch experts early;
    of another width where the experts work in a latent space: router [Dr,
    E]) and x itself where it is None.

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
    The row buffer is min(top_k, H) * N rows, because every one of a
    token's choices may be held (1.5 N on average at 6 of 64 with 16 held)
    and its choices are distinct experts, so no more than H of them are
    (22 of 512 a token on 8 held: 8 N rows and not 22 N).

    Which numbering holds is `numbered_by`'s to say, from the shapes: where
    a share is held and H < top_k the assignments are numbered by held
    expert, a = h * N + token with h in [0, H), H * N of them
    (`_by_held_expert`): one compare over [H, top_k, N] says whether the
    token chose expert first_expert + h and with what weight, [H, N] each
    (jax transposes it, so a weight's gradient goes back to its slot and on
    into the router). `rank` is a running sum of that membership, `order`
    the numbering sorted by it. `_token_places`, the windows of
    `_token_sum` and the loops over the held rows then run as they do by
    slot, over H * N integers and H "slots" a token; the buffer is
    `order`'s own length, and nothing is numbered that cannot be held. With
    H >= top_k, and wherever every expert is held, the numbering is the
    slot-major one, the shorter there. The two differ in the order of
    float32 sums and in nothing else: numbered by expert a group's rows lie
    by token (by slot, then token, numbered by slot), so the weights'
    gradients sum an expert's rows in another order, and a token's held
    rows are summed in expert order (in score order, numbered by slot).

    Between the router's top-k and the rows' passes no scalar is moved by
    index, under any numbering: XLA's gather and scatter of 32-bit scalars
    cost the v5e 8-10 and 5 ns an index, serially (`_sorted_by`). What a
    token reads at the experts it chose, its scores, is a COMPARE of the
    choices against the experts' axis and a sum of one term (`_chosen`, as
    `load` and `_by_held_expert` are compares), and jax's transpose of it
    puts the weights' gradients back into [N, E] without a scatter-add. What
    is permuted is SORTED (`_sorted_by`): `rank`, the inverse of `order`, is
    arange sorted by `order` (numbered by expert `order` is the numbering
    sorted by `rank`); going back, the weights come to their sorted rows by
    a sort keyed on `rank` and their gradients return by one keyed on
    `order` (`_combine_bwd`, one form for the three numberings). Each is the
    gather or scatter it replaced to the bit. `order` and what the rows'
    loops read going forward are functions of the integers alone: the grad
    op replays this rule, and XLA merges the replay with the forward op's
    operations only where they are the same (PR 62).

    Four permutations move rows, a layer's forward and backward, and where
    a share is held (`rows_moved`) all four, and the elementwise passes
    between them, touch only the held rows, in loops whose trip count is
    known at run time: the share of the buffer they touch is
    ExpertLoad[first_expert : first_expert + H].sum() / (top_k * N), a
    value every caller can fetch. The two that produce sorted rows
    (`_held_rows` forward, `_held_weighted` in `_combine`'s backward) gather
    the tiles of `ROW_TILE` rows below total = sizes.sum(); `_held_rows`
    leaves what its buffer held from the last such tile on (zeros on
    `ragged_dot`'s route, nothing at all on the kernels':
    `_sorted_rows_start`), `_held_weighted` writes over y and leaves what
    y held. The two that produce a token's rows (`_combine` forward,
    `_held_experts` backward) number the held assignments token-major on
    [N] integers (`_token_places`) and, `SUM_TILE` places a trip, gather
    their rows and sum them by token with a one-hot matmul (`_token_sum`):
    about 50 ns a gathered row on the v5e whatever the form, so the held
    rows' gather is what is left of them. The two d rows that `w_gate`'s and
    `w_up`'s transposes return are added where that sum reads them, and
    `_gated`'s transpose runs in the held tiles, d gate written over d
    hidden. `_gated` forward is a pass over all A rows on `ragged_dot`'s
    route alone: on the kernels' it is the epilogue of the ONE kernel that
    multiplies a row tile by `w_gate` and `w_up` (expert_gmm.gmm_unit: the
    accumulators rounded to the experts' dtype first and the unit taken of
    the rounded values, so `gate`, `up` and the hidden rows are the
    separate passes' to the bit), and the two buffers a loop over the held
    tiles used to fill with zeros first (`_held_rows`' rows, d up) start as
    a call's output that nothing wrote, so on that route nothing outside a
    tile below `total` is written or read, forward or backward. What such
    a start reads is the integers' alone, as `order` is: the replay's and
    the forward op's are one to XLA. Where every expert is held the
    four are gathers of the whole buffer: the two token-side ones sum over
    the leading axis of [top_k, N, D], which is the buffer as it lies
    (numbered token-major, top_k = 6 made each of them a relayout of the
    buffer: 6 rows do not fill a tile of 8; PERF.md section 6, PR 32).

    The rows past `total` belong to no group. What `ragged_dot` does with
    them depends on the backend: on the CPU it writes zeros there and its
    transpose gives them a zero gradient; on the v5e it neither reads nor
    writes them, so its time follows the groups' sum and not the buffer
    (0.75 ms for 12288 of 49152 rows of [2560] x [16, 2560, 768], 2.03 ms
    for all 49152), and they hold whatever the buffer held before, in the
    output and in the gradient of its left operand alike (my chip run, PR
    31). So nothing rests on them: no pass reads a row from `total` on into
    a token's sum or a weight's gradient, forward or backward, so no NaN in
    an unwritten row reaches either. The kernels of ops/expert_gmm.py, which
    run the nine matmuls on one TPU (`matmul_route`; `mesh` is the step's,
    None on one device), do as the v5e's `ragged_dot` does: a tile of rows
    wholly past `total` is neither read nor written.

    The router's matmul, softmax and top-k are float32 at full precision
    whatever x's dtype; the experts compute in `expert_dtype` (x's own if
    None; bfloat16 under AMP) and the top_k weighted outputs of a token are
    summed in float32.

    `scoring` "softmax" scores a token's experts by the softmax of its
    router logits, "sigmoid" by s = sigmoid(logits), each expert on its own.
    `expert_bias` [E], where given, enters the choice and nothing else: the
    top_k is over s + b and the weights are read from s, so the bias
    moves which experts run and never how much one counts, and it has no
    gradient (its only use is an argument of top_k; the router's gradient
    comes through s). With norm_topk_prob a sigmoid router divides by the
    chosen scores' sum + SIGMOID_NORM_EPS (+ `norm_eps` where it is given:
    DeepSeek-V3's is 1e-20). `scale` multiplies the weights last. Under sigmoid scoring the balance and z terms are zeros: both are
    defined on a softmax's probabilities and its logsumexp.

    `groups` (n_group, topk_group), where given, is DeepSeek-V3's group
    limit on the choice (`_group_limited`): the top k is taken inside a
    token's topk_group best of n_group runs of neighbouring experts, a
    group scored by the sum of its two largest s + b. In a share the held
    experts are neighbours and so lie in few groups (0-7 of 512 all in
    group 0 of 8): a token whose kept groups exclude them has no assignment
    here and gets zeros from this chip, its weights for the experts it did
    choose renormalised over those as on every chip; `load` counts its
    assignments where they went. The shares still add up to the whole
    layer: the choice is every chip's own and the same on all of them.

    Returns (out [N, D] in the experts' dtype,
             load-balance term [1]: E * sum_e (c_e / N) * mean_n p[n, e],
             router z term [1]: mean_n logsumexp(logits[n])^2,
             c [E] int32: assignments an expert over ALL E, sum = top_k * N;
             c[first_expert : first_expert + H] are the rows computed).
    """
    n = x.shape[0]
    e, held = router.shape[1], w_up.shape[0]
    if not 0 <= first_expert <= e - held:
        raise ValueError("routed_ffn holds experts %d..%d of %d"
                         % (first_expert, first_expert + held - 1, e))
    dtype = jnp.dtype(expert_dtype or x.dtype)
    logits = jnp.dot((x if router_x is None else router_x)
                     .astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    # norm_eps only where it is given: benchmark/tests/mutant_lfm2.py stands
    # a `_route` of the six arguments above in this one's place
    probs, lse, gate, expert = _route(
        logits, top_k, norm_topk_prob, scoring, expert_bias, scale,
        **({} if norm_eps is None else {"norm_eps": norm_eps}),
        **({} if groups is None else {"groups": groups}))

    # assignment a = j * N + n (slot-major); `order` lists the assignments by
    # expert (stable, so by slot then token inside an expert), `rank` is
    # where each went
    by = numbered_by(e, held, top_k)
    expert, gate = expert.T.reshape(-1), gate.T
    load = jnp.sum(expert[:, None] == jnp.arange(e), axis=0, dtype=jnp.int32)
    if by is None:
        sort_key, sizes, total = expert, load, None
    else:
        local = expert - first_expert
        if by == "slot":
            here = (local >= 0) & (local < held)
            sort_key = jnp.where(here, local, held)    # not held: past them
        sizes = load[first_expert:first_expert + held]
        total = sizes.sum()
    if by == "expert":
        # a = h * N + n, h the held expert: held * N assignments, not top_k * N
        gate, order, rank = _by_held_expert(
            local.reshape(top_k, n), gate, sizes)
    else:
        order = jnp.argsort(sort_key, stable=True)
        rank, = _sorted_by(order, jnp.arange(order.shape[0],
                                             dtype=order.dtype))

    # the kernels' visits, once for the layer's nine matmuls
    plan = None
    if matmul_route(x.shape[1], w_up.shape[2], dtype, mesh) \
            == KERNEL_MATMUL:
        from ..ops import expert_gmm
        plan = expert_gmm.plan(sizes, order.shape[0])
    if total is None:
        rows, places = _dispatch(x.astype(dtype), order, rank), None
        if plan is not None:
            hidden = _kernel_unit(
                rows, None if w_gate is None else w_gate.astype(dtype),
                w_up.astype(dtype), plan, _unit(activation,
                                                w_gate is not None))
        elif w_gate is None:
            hidden = _ungated_unit(activation)(
                _grouped_matmul(rows, w_up.astype(dtype), sizes, plan))
        else:
            hidden = _gated(
                _grouped_matmul(rows, w_gate.astype(dtype), sizes, plan),
                _grouped_matmul(rows, w_up.astype(dtype), sizes, plan),
                activation)
        y = _grouped_matmul(hidden, w_down.astype(dtype), sizes, plan)
    else:
        places = _token_places(rank, total, gate.shape[0])
        y = _held_experts(x.astype(dtype),
                          None if w_gate is None else w_gate.astype(dtype),
                          w_up.astype(dtype), w_down.astype(dtype), order,
                          rank, sizes, plan, total, places, activation)
    out = _combine(y, gate, order, rank, total, places)

    if scoring == "sigmoid":
        return out, jnp.zeros((1,), jnp.float32), \
            jnp.zeros((1,), jnp.float32), load
    balance = e * jnp.sum(load.astype(jnp.float32) / n * probs.mean(0))
    z = jnp.mean(jnp.square(lse))
    return out, balance.reshape(1), z.reshape(1), load
