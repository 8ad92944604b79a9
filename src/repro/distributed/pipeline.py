"""Pipeline parallelism utility (GPipe-style microbatching over a mesh axis).

The fixed 256/512-chip production mesh does not need PP for the assigned
archs (TP=16 x FSDP=16 fits every memory table row — see EXPERIMENTS.md),
but >4k-chip scaling would add a "pipe" axis; this module provides the
building block and is covered by tests on host sub-meshes.

Implementation: shard_map over the ``pipe`` axis. Stage i holds its stage
params (stacked layer params sharded on the pipe axis). The classic skewed
loop runs M + D - 1 ticks; activations hop stage-to-stage with
collective_permute. Backward is JAX autodiff through the loop (ppermute is
linear, so the transpose is the reverse pipeline — a fill/drain schedule
equivalent to GPipe; 1F1B re-ordering is an XLA scheduling concern).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_apply(
    stage_fn: Callable,     # (stage_params, x) -> y   (one stage's compute)
    stage_params,           # pytree, leaves stacked on leading pipe dim
    x_micro: jax.Array,     # [M, mb, ...] microbatched input
    mesh: Mesh,
    axis: str = "pipe",
):
    """Run M microbatches through D pipeline stages; returns [M, mb, ...]."""
    D = mesh.shape[axis]

    def local(params_stage, x_all):
        # params_stage: this stage's params (leading pipe dim stripped to 1)
        params_stage = jax.tree_util.tree_map(lambda p: p[0], params_stage)
        M = x_all.shape[0]
        idx = jax.lax.axis_index(axis)
        perm = [(i, (i + 1) % D) for i in range(D)]
        ticks = M + D - 1
        buf = jnp.zeros_like(x_all[0])
        outs = jnp.zeros_like(x_all)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if in range); others use buf
            mb_in = jax.lax.dynamic_index_in_dim(
                x_all, jnp.clip(t, 0, M - 1), keepdims=False
            )
            x_in = jnp.where(idx == 0, mb_in, buf)
            active = (t - idx >= 0) & (t - idx < M)
            y = stage_fn(params_stage, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # last stage writes its finished microbatch t - (D-1)
            out_slot = jnp.clip(t - (D - 1), 0, M - 1)
            write = (idx == D - 1) & (t >= D - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, y, outs[out_slot]), out_slot, axis=0
            )
            # hop activations rightward
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.ppermute(outs, axis, [((D - 1 + i) % D, i) for i in range(D)])
        return outs

    shmap = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(axis), P(None)),
        out_specs=P(None),
        check_vma=False,
    )
    return shmap(stage_params, x_micro)
