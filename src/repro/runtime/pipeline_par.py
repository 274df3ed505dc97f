"""Optional GPipe-style pipeline parallelism over a "stage" mesh axis.

The assigned production mesh has no stage axis (DP x TP covers the 40-cell
dry-run), but at >=1000-node scale cross-pod TP is infeasible and PP becomes
the inter-pod axis.  This module implements the classic microbatch-rotation
schedule with ``shard_map`` + ``jax.lax.ppermute``:

  * layers are split into S contiguous stages; stage s holds its slice of the
    layer-stacked params (shard over the stage axis — no replication);
  * the microbatch "belt" rotates activations stage->stage+1 each tick;
  * S warmup + S cooldown bubbles, standard GPipe efficiency M/(M+S-1).

Tested on a forced-8-device CPU mesh in tests/test_distributed.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(layer_fn, params_stacked, x_microbatches, mesh: Mesh,
                   stage_axis: str = "stage"):
    """Run ``y = layer_fn(p_layer, x)`` through a pipeline.

    params_stacked: pytree with leading dim L (= S * layers_per_stage).
    x_microbatches: (M, mb, ...) — M microbatches.
    Returns (M, mb, ...) outputs, pipelined over the ``stage_axis`` of mesh.
    """
    S = mesh.shape[stage_axis]
    M = x_microbatches.shape[0]
    L = jax.tree_util.tree_leaves(params_stacked)[0].shape[0]
    assert L % S == 0, (L, S)
    per_stage = L // S

    def stage_fn(p_stage, xs):
        # p_stage: (per_stage, ...) slice on this stage; xs: (M, mb, ...)
        def run_stage(x):
            def body(h, pl):
                return layer_fn(pl, h), None
            h, _ = jax.lax.scan(body, x, p_stage)
            return h

        stage_id = jax.lax.axis_index(stage_axis)
        n_ticks = M + S - 1
        buf = jnp.zeros_like(xs[0])

        def tick(carry, t):
            buf, ys = carry
            # stage 0 ingests microbatch t (if any); others take the rotated belt
            feed = jnp.where(t < M, t, 0)
            inject = xs[feed]
            h_in = jnp.where(stage_id == 0, inject, buf)
            h_out = run_stage(h_in)
            # rotate belt to the next stage
            nxt = jax.lax.ppermute(
                h_out, stage_axis,
                [(i, (i + 1) % S) for i in range(S)])
            # ONLY the last stage emits microbatch t-(S-1); other stages'
            # ys buffers stay zero and vanish in the cross-stage psum below.
            emit_idx = t - (S - 1)
            emit = jnp.logical_and(stage_id == S - 1, emit_idx >= 0)
            ys = jax.lax.cond(
                emit,
                lambda ys: ys.at[jnp.maximum(emit_idx, 0)].set(h_out),
                lambda ys: ys, ys)
            return (nxt, ys), None

        ys0 = jnp.zeros_like(xs)
        (_, ys), _ = jax.lax.scan(tick, (buf, ys0), jnp.arange(n_ticks))
        # replicate the last stage's emissions to every device
        return jax.lax.psum(ys, stage_axis)

    spec_p = jax.tree_util.tree_map(
        lambda l: P(stage_axis, *([None] * (l.ndim - 1))), params_stacked)
    out = jax.shard_map(
        stage_fn, mesh=mesh,
        in_specs=(spec_p, P()),            # belt replicated; params staged
        out_specs=P(),
        check_vma=False,
    )(params_stacked, x_microbatches)
    return out


def pipeline_bubble_fraction(n_micro: int, n_stages: int) -> float:
    """GPipe bubble overhead: (S-1)/(M+S-1)."""
    return (n_stages - 1) / (n_micro + n_stages - 1)
