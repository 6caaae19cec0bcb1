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
exchange.
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(x, index, inverse, repeat):
    """repeat(x, repeat, axis=0)[index] for a permutation `index` of those
    rows whose inverse is `inverse`, as one gather. The gradient of a gather
    is a scatter-add; of a permutation it is the gather by the inverse, then
    the sum over a row's `repeat` copies, which is what the backward rule
    does."""
    return x[index // repeat]


def _take_rows_fwd(x, index, inverse, repeat):
    return x[index // repeat], inverse


def _take_rows_bwd(repeat, inverse, g):
    g = g[inverse]
    if repeat > 1:
        g = g.reshape(-1, repeat, g.shape[-1]).sum(1, dtype=jnp.float32) \
            .astype(g.dtype)
    return g, None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


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


def routed_ffn(x, router, w_gate, w_up, w_down, top_k, norm_topk_prob=False,
               expert_dtype=None, router_x=None, activation="silu",
               first_expert=0):
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
    there is no capacity. The assignments are sorted by expert, those to
    experts that are not held last, and the three expert matmuls run grouped
    over the sorted rows (`_grouped_matmul`) with the held experts' counts
    as group sizes, so they cost the held assignments' operations and not
    the stored experts'. The row buffer is top_k * N rows whatever H is,
    because every one of a token's choices may be held (1.5 N on average at
    6 of 64 with 16 held); the rows past the groups' sum belong to no group.
    What `ragged_dot` does with them depends on the backend: on the CPU it
    writes zeros there and its transpose gives them a zero gradient; on the
    v5e it neither reads nor writes them, so its time follows the groups'
    sum and not the buffer (0.75 ms for 12288 of 49152 rows of [2560] x
    [16, 2560, 768], 2.03 ms for all 49152), and they hold whatever the
    buffer held before, in the output and in the gradient of its left
    operand alike (my chip run, PR 31). So nothing may rest on them: the
    gathered rows past the sum are set to zero before the first matmul,
    which makes their gradient zero whatever the matmul's transpose left
    there, before `_take_rows`' backward adds a row's gradient into its
    token's; and the combine selects (not multiplies) the held assignments'
    outputs, so no NaN in an unwritten row reaches a token.

    The router's matmul, softmax and top-k are float32 at full precision
    whatever x's dtype; the experts compute in `expert_dtype` (x's own if
    None; bfloat16 under AMP) and the top_k weighted outputs of a token are
    summed in float32.

    Returns (out [N, D] in the experts' dtype,
             load-balance term [1]: E * sum_e (c_e / N) * mean_n p[n, e],
             router z term [1]: mean_n logsumexp(logits[n])^2,
             c [E] int32: assignments an expert over ALL E, sum = top_k * N;
             c[first_expert : first_expert + H] are the rows computed).
    """
    n, d = x.shape
    e, held = router.shape[1], w_gate.shape[0]
    if not 0 <= first_expert <= e - held:
        raise ValueError("routed_ffn holds experts %d..%d of %d"
                         % (first_expert, first_expert + held - 1, e))
    dtype = jnp.dtype(expert_dtype or x.dtype)
    logits = jnp.dot((x if router_x is None else router_x)
                     .astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    lse = jax.nn.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    gate, expert = jax.lax.top_k(probs, top_k)             # [N, top_k]
    if norm_topk_prob:
        gate = gate / gate.sum(-1, keepdims=True)

    # assignment a = n * top_k + j; `order` lists the assignments by expert
    # (stable, so by token inside an expert), `rank` is where each went
    expert = expert.reshape(-1)
    if held == e:
        here, sort_key = None, expert
    else:
        local = expert - first_expert
        here = (local >= 0) & (local < held)
        sort_key = jnp.where(here, local, held)    # not held: past the groups
    order = jnp.argsort(sort_key, stable=True)
    rank = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype))
    load = jnp.sum(expert[:, None] == jnp.arange(e), axis=0, dtype=jnp.int32)
    sizes = load if here is None else load[first_expert:first_expert + held]

    rows = _take_rows(x.astype(dtype), order, rank, top_k)
    if here is not None:
        in_group = jnp.arange(rows.shape[0]) < sizes.sum()
        rows = jnp.where(in_group[:, None], rows, 0)
    hidden = _gated(_grouped_matmul(rows, w_gate.astype(dtype), sizes),
                    _grouped_matmul(rows, w_up.astype(dtype), sizes),
                    activation)
    y = _grouped_matmul(hidden, w_down.astype(dtype), sizes)
    y = _take_rows(y, rank, order, 1).reshape(n, top_k, d).astype(jnp.float32)
    if here is not None:
        y = jnp.where(here.reshape(n, top_k, 1), y, 0.0)
    out = jnp.sum(y * gate[:, :, None], axis=1).astype(dtype)

    balance = e * jnp.sum(load.astype(jnp.float32) / n * probs.mean(0))
    z = jnp.mean(jnp.square(lse))
    return out, balance.reshape(1), z.reshape(1), load
