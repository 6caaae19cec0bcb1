"""Pipeline parallelism (`pp` mesh axis): GPipe-style looped pipeline.

TPU-first addition (SURVEY.md §2 "DP/TP/PP/SP composable on one Mesh"). The
reference (mozga-intel/Paddle, March 2018) predates pipeline parallelism —
its only model-partitioning story is the pserver split
(python/paddle/fluid/distribute_transpiler.py), which shards *parameters*,
not *stages*. Here stages are real: layer s of a homogeneous stack lives on
pipeline rank s, microbatches stream through the ring, and activations hop
stage→stage over ICI via `lax.ppermute` while every chip stays busy (after
the S-1-step fill bubble).

Design (the scaling-book looped-pipeline recipe):
- stage parameters are STACKED on a leading [S, ...] dim and sharded
  P('pp') — each chip holds exactly its stage's weights, no replication.
- the schedule is one `lax.scan` of length M + S - 1 (M microbatches):
  chip s computes microbatch t-s at step t; a single collective-permute per
  step shifts activations forward one stage. Bubble steps compute garbage
  that is `where`-masked out of the output buffer — static shapes, no
  data-dependent control flow, exactly what XLA wants.
- outputs accumulate on the last stage and are `psum`-broadcast over the
  ring at the end (zeros elsewhere), so the caller sees a replicated
  [B, ...] result it can feed a loss head.
- fully differentiable: the vjp of ppermute is the reverse permute, so
  jax.grad produces the backward pipeline (reverse schedule) automatically
  — no hand-written 1F1B machinery.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map

from .mesh import P, vary as _vary

__all__ = ["pipeline_apply", "pipeline_stages_spec", "stack_stage_params",
           "sequential_reference", "mlp_block_init", "mlp_block_apply",
           "mlp_block_specs"]


# ---------------------------------------------------------------------------
# The homogeneous pipeline STAGE block (absorbed from the seed-era
# parallel/tp.py — see MIGRATION.md). Program-level tensor parallelism
# is `ShardingPlan.build(..., tp_axis=)` (plan.py, ARCHITECTURE.md §23);
# these helpers survive only as the manual-mode stage math the pipeline
# schedule composes with: a Megatron-style column/row two-matmul block
# with one psum, runnable densely (tp_axis=None — the single-chip
# reference) or manually inside shard_map (a pipeline stage, where the
# 'pp' schedule is already manual and GSPMD can't place the collective).
# ---------------------------------------------------------------------------

def mlp_block_init(rng, d, d_hidden, scale=0.1):
    """Params for one tanh MLP block: [d -> d_hidden -> d] (shape-
    preserving, so it can serve as a homogeneous pipeline stage)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(rng)
                              if isinstance(rng, int) else rng)
    return {
        "w1": jax.random.normal(k1, (d, d_hidden), jnp.float32) * scale,
        "b1": jnp.zeros((d_hidden,), jnp.float32),
        "w2": jax.random.normal(k2, (d_hidden, d), jnp.float32) * scale,
        "b2": jnp.zeros((d,), jnp.float32),
    }


def mlp_block_specs(tp_axis="mp", pp_axis=None):
    """PartitionSpecs for (optionally stage-stacked) mlp_block params.

    Column-parallel w1/b1 split the hidden dim over ``tp_axis``; the
    row-parallel w2 splits its input (hidden) dim; b2 is replicated over
    mp (added after the psum). With ``pp_axis`` set, a leading stacked
    stage dim is sharded over it (pipeline composition — the
    `pipeline_apply(param_specs=...)` hook)."""
    def pp(*rest):
        return P(pp_axis, *rest) if pp_axis else P(*rest)
    return {
        "w1": pp(None, tp_axis),
        "b1": pp(tp_axis),
        "w2": pp(tp_axis, None),
        "b2": pp(None),
    }


def mlp_block_apply(params, x, tp_axis=None):
    """y = w2ᵀ·tanh(w1ᵀx + b1) + b2, with the hidden dim sharded over
    ``tp_axis`` when running manually inside shard_map (one psum — the
    Megatron pattern). With tp_axis=None this is the dense math (the
    single-chip reference, or a plain stage under the stacked 'pp'
    placement)."""
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    z = h @ params["w2"]
    if tp_axis is not None:
        z = lax.psum(z, tp_axis)
    return z + params["b2"]


def sequential_reference(stage_fn, stacked_params, x):
    """Single-device reference: apply the S stages in order."""
    S = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    out = x
    for s in range(S):
        p = jax.tree_util.tree_map(lambda a: a[s], stacked_params)
        out = stage_fn(p, out)
    return out


def stack_stage_params(per_stage_params):
    """[params_stage0, params_stage1, ...] -> one pytree with leading S dim
    (what pipeline_apply shards over 'pp')."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def pipeline_stages_spec(stacked_params, axis="pp"):
    """PartitionSpecs placing each stage's slice of the stacked params on its
    pipeline rank (leading dim sharded, everything else replicated)."""
    return jax.tree_util.tree_map(lambda _: P(axis), stacked_params)


def _pipeline_shard(params, xs, stage_fn, axis_name, vary_axes):
    """Per-shard body. params: stage-stacked pytree, locally [1, ...];
    xs: [M, mb, ...] microbatches (replicated over the pipeline axis).
    Returns [M, mb, ...] outputs, identical on every pipeline rank."""
    n = lax.psum(1, axis_name)
    s = lax.axis_index(axis_name)
    M = xs.shape[0]
    p_local = jax.tree_util.tree_map(lambda a: a[0], params)

    state0 = _vary(jnp.zeros(xs.shape[1:], xs.dtype), vary_axes)
    outs0 = _vary(jnp.zeros(xs.shape, xs.dtype), vary_axes)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, t):
        state, outs = carry
        mb = jnp.clip(t, 0, M - 1)
        # first stage consumes fresh microbatches; others the activation
        # ppermuted in from the previous stage last step
        inp = jnp.where(s == 0, xs[mb], state)
        y = stage_fn(p_local, inp)
        out_idx = t - (n - 1)
        oc = jnp.clip(out_idx, 0, M - 1)
        take = (s == n - 1) & (out_idx >= 0)
        outs = outs.at[oc].set(jnp.where(take, y, outs[oc]))
        state_next = lax.ppermute(y, axis_name, perm)
        return (state_next, outs), None

    (_, outs), _ = lax.scan(step, (state0, outs0),
                            jnp.arange(M + n - 1))
    # only the last stage wrote anything; psum replicates it ring-wide
    return lax.psum(outs, axis_name)


def pipeline_apply(stage_fn, stacked_params, x, mesh, num_microbatches=None,
                   axis="pp", batch_axis=None, param_specs=None):
    """Run x through S pipeline stages sharded over mesh axis `axis`.

    stage_fn(params, x_mb) -> y_mb must be shape-preserving (homogeneous
    stages — the classic pipeline regime). stacked_params: pytree with
    leading dim S == mesh.shape[axis] (see stack_stage_params). x: global
    [B, ...] batch, B divisible by num_microbatches (default S, the minimum
    that keeps every stage busy; more microbatches shrink the bubble
    fraction (S-1)/(M+S-1)). batch_axis: optional mesh axis ('dp') to
    additionally shard the microbatch dim — dp×pp composition on one mesh.

    param_specs: optional PartitionSpec pytree (same structure as
    stacked_params) overriding the default P(axis)-on-the-stage-dim
    placement — the dp×mp×pp composition hook: shard stage weights over
    BOTH 'pp' and a tensor-parallel axis (e.g. mlp_block_specs(
    tp_axis='mp', pp_axis='pp')) and have stage_fn do its own mp
    collectives (mlp_block_apply(..., tp_axis='mp')).

    Differentiable end to end; jit-compatible (call under the mesh).
    """
    S = mesh.shape[axis]
    leading = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if leading != S:
        raise ValueError(
            "stacked_params leading dim %d != pipeline size %d" %
            (leading, S))
    M = num_microbatches if num_microbatches is not None else S
    B = x.shape[0]
    if B % M:
        raise ValueError("batch %d not divisible into %d microbatches"
                         % (B, M))
    xs = x.reshape((M, B // M) + x.shape[1:])

    vary_axes = (axis,) if batch_axis is None else (axis, batch_axis)
    x_spec = P(None, batch_axis) if batch_axis else P()
    fn = shard_map(
        functools.partial(_pipeline_shard, stage_fn=stage_fn,
                          axis_name=axis, vary_axes=vary_axes),
        mesh=mesh,
        in_specs=(param_specs if param_specs is not None
                  else pipeline_stages_spec(stacked_params, axis), x_spec),
        out_specs=x_spec)
    out = fn(stacked_params, xs)
    return out.reshape((B,) + out.shape[2:])
