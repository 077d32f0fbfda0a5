"""Pipeline parallelism over a mesh axis (GPipe schedule, shard_map-native).

``pipeline_apply`` runs inside ``shard_map`` over the pipeline axis: each
device group holds one *stage* (a slice of the layer stack) and microbatches
flow stage→stage via ``lax.ppermute``.  The schedule is the classic GPipe
bubble: T = M + S − 1 ticks for M microbatches over S stages; reverse-mode
autodiff differentiates straight through (ppermute's transpose is the
reversed permutation), yielding the symmetric backward schedule for free.

Intended placement (multi-pod mesh): map the ``pod`` axis to stages when the
cross-pod link is too slow for a per-step gradient all-reduce — then only
microbatch activations cross pods, once per tick.  The default remains
pod-DP; flip with ``launch.train --pp``-style wiring or use this primitive
directly.  Bubble fraction = (S−1)/(M+S−1) — pick M ≥ 4·S.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,  # (stage_params, x_mb) -> y_mb
    stage_params,  # params of MY stage (leading stage dim already split)
    x_mb: jax.Array,  # (M, mb, ...) microbatched input (stage 0 consumes)
    *,
    axis_name: str,
    num_stages: int,
) -> jax.Array:
    """Returns (M, mb, ...) last-stage outputs. Call inside shard_map."""
    s = jax.lax.axis_index(axis_name)
    M = x_mb.shape[0]
    T = M + num_stages - 1
    mb_shape = x_mb.shape[1:]

    fwd = [(i, i + 1) for i in range(num_stages - 1)]

    def tick(t, carry):
        buf, outs = carry  # buf: (mb, ...) current input for my stage
        # stage 0 injects microbatch t (clamped; inactive ticks are ignored)
        inject = jax.lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
        )
        buf = jnp.where(s == 0, inject, buf)
        y = stage_fn(stage_params, buf)
        # last stage records its result at position t-(S-1) when active
        write_at = jnp.clip(t - (num_stages - 1), 0, M - 1)
        active_out = jnp.logical_and(s == num_stages - 1, t >= num_stages - 1)
        cur = jax.lax.dynamic_index_in_dim(outs, write_at, 0, keepdims=False)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, jnp.where(active_out, y, cur), write_at, 0
        )
        # hand my activation to the next stage
        buf_next = jax.lax.ppermute(y, axis_name, fwd)
        return buf_next, outs

    buf0 = jnp.zeros(mb_shape, x_mb.dtype)
    outs0 = jnp.zeros((M,) + jax.eval_shape(stage_fn, stage_params, buf0).shape, x_mb.dtype)
    _, outs = jax.lax.fori_loop(0, T, tick, (buf0, outs0))
    return outs


def make_pipelined_loss(
    stage_fn: Callable,  # (stage_params, x) -> x  (homogeneous stages)
    loss_head: Callable,  # (head_params, y_mb, target_mb) -> scalar
    mesh,
    axis_name: str = "pod",
):
    """Builds loss(params, batch) where params = {"stages": (S, ...) stacked
    stage params, "head": head params}; batch = {"x": (M, mb, ...),
    "y": (M, mb, ...)}.  Stages shard over ``axis_name``; the head lives on
    the last stage and the scalar loss is psum-broadcast so every stage
    returns the same value (grads flow to every stage's params)."""
    num_stages = mesh.shape[axis_name]

    def loss(params, batch):
        def shmapped(stages, head, x_mb, y_mb):
            my_stage = jax.tree_util.tree_map(lambda a: a[0], stages)
            outs = pipeline_apply(
                stage_fn, my_stage, x_mb, axis_name=axis_name, num_stages=num_stages
            )
            s = jax.lax.axis_index(axis_name)
            per_mb = loss_head(head, outs, y_mb)
            val = jnp.where(s == num_stages - 1, per_mb, 0.0)
            return jax.lax.psum(val, axis_name)[None]

        specs_stages = jax.tree_util.tree_map(lambda _: P(axis_name), params["stages"])
        specs_head = jax.tree_util.tree_map(lambda _: P(), params["head"])
        out = jax.shard_map(
            shmapped,
            mesh=mesh,
            in_specs=(specs_stages, specs_head, P(), P()),
            out_specs=P(axis_name),
            check_vma=False,
        )(params["stages"], params["head"], batch["x"], batch["y"])
        return out.mean()

    return loss
