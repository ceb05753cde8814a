"""Partial pivoted Cholesky decomposition (paper §4.1 / Appendix C).

Computes a rank-k approximation K ≈ L_k L_kᵀ by greedily eliminating the
largest remaining diagonal entry.  Only needs *blackbox row access*
``row(i) → K[i, :]`` and ``diag() → diag(K)`` — never the full matrix —
so it costs O(ρ(K)·k + n·k²) where ρ(K) is the cost of one row
(paper Observation 4.1).

Sequential in k by nature (k ≤ ~10 in practice), so a ``lax.fori_loop`` of
row accesses is the right TPU mapping; its cost is negligible next to a
single kernel matmul, matching the paper's claim.

``pivoted_cholesky_sharded`` row-partitions the O(n·k) per-pivot work
(residual update, column write, diagonal decrement) over the mesh data
axes with shard_map — the last replicated O(n) stage of the BBMM solve
path at n ≥ 10⁶.  Per pivot the collectives are O(shards + k): an
all-gather of the (local max, argmax) pair to elect the global pivot and a
psum that broadcasts the pivot's k-vector L[piv] from its owning shard.
The pivot ROW K[piv, :] is recomputed replicated (O(n·ρ) each, where ρ is
the per-entry kernel cost) — that stage is matmul-shaped and cheap; it is
the n-length *state updates* that had to stop being replicated.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


@partial(jax.jit, static_argnames=("row_fn", "rank"))
def pivoted_cholesky(
    row_fn: Callable[[jax.Array], jax.Array],
    diag: jax.Array,
    rank: int,
    *,
    jitter: float = 1e-8,
) -> jax.Array:
    """Rank-`rank` pivoted Cholesky of the PSD matrix defined by row_fn/diag.

    Args:
      row_fn: ``i ↦ K[i, :]`` (traced index).
      diag: (n,) diagonal of K.
      rank: number of pivots k.

    Returns:
      L: (n, k) such that K ≈ L @ L.T (cols beyond numerical rank are 0).
    """
    n = diag.shape[0]
    dtype = jnp.promote_types(diag.dtype, jnp.float32)
    diag = diag.astype(dtype)

    L0 = jnp.zeros((n, rank), dtype)
    d0 = diag
    picked0 = jnp.zeros((n,), bool)

    def body(j, carry):
        L, d, picked = carry
        d_masked = jnp.where(picked, -jnp.inf, d)
        piv = jnp.argmax(d_masked)
        dpiv = jnp.clip(d[piv], 0.0)
        ok = dpiv > jitter  # stop producing columns once residual exhausted
        sqrt_piv = jnp.sqrt(jnp.where(ok, dpiv, 1.0))

        row = row_fn(piv).astype(dtype)  # K[piv, :]
        # residual row: K[piv,:] - L[piv,:] @ L.T   (columns ≥ j are zero)
        resid = row - L @ L[piv]
        col = resid / sqrt_piv
        col = jnp.where(picked, 0.0, col)  # exact zeros at eliminated pivots
        col = col.at[piv].set(sqrt_piv)
        col = jnp.where(ok, col, 0.0)

        L = L.at[:, j].set(col)
        d = d - col * col
        picked = picked.at[piv].set(True)
        return (L, d, picked)

    L, _, _ = jax.lax.fori_loop(0, rank, body, (L0, d0, picked0))
    return L


def pivoted_cholesky_dense(K: jax.Array, rank: int, **kw) -> jax.Array:
    """Convenience wrapper for an explicit matrix (tests / small n)."""
    return pivoted_cholesky(lambda i: K[i], jnp.diagonal(K), rank, **kw)


def pivoted_cholesky_sharded(
    base_op,
    rank: int,
    *,
    jitter: float = 1e-8,
    mesh=None,
    axes: tuple = ("data",),
) -> jax.Array:
    """Row-sharded rank-`rank` pivoted Cholesky of a LinearOperator.

    Each shard owns a contiguous block of the n rows of (L, d, picked);
    per pivot it elects the global maximum-diagonal row via an all-gather
    of (local max, local argmax), fetches L[piv] from the owning shard via
    a masked psum, and performs its O(n_loc·k) share of the residual /
    column / diagonal updates locally.  Matches the replicated
    :func:`pivoted_cholesky` to floating-point reassociation error.

    Args:
      base_op: LinearOperator with ``row(i)`` / ``diagonal()`` (gradients
        are stopped — the preconditioner is constant under autodiff, same
        contract as the replicated path).
      rank: number of pivots k.
      mesh: mesh to shard over (default: the live mesh).
      axes: mesh axes sharding the n rows; n must divide their product.

    Returns:
      L: (n, k), row-sharded over ``axes``.
    """
    from repro.distributed.sharding import (
        current_mesh,
        mesh_axis_sizes,
        unchecked_shard_map,
    )

    mesh = mesh if mesh is not None else current_mesh()
    if mesh is None:
        raise ValueError("pivoted_cholesky_sharded needs a live (or explicit) mesh")
    sizes = mesh_axis_sizes(mesh)
    shards = 1
    for a in axes:
        shards *= sizes[a]
    diag = jax.lax.stop_gradient(base_op.diagonal())
    n = diag.shape[0]
    if n % shards != 0:
        raise ValueError(f"n={n} not divisible by {shards} row shards")
    n_loc = n // shards
    dtype = jnp.promote_types(diag.dtype, jnp.float32)
    # operator leaves enter as explicit replicated operands (shard_map
    # cannot close over traced values)
    leaves, treedef = jax.tree_util.tree_flatten(jax.lax.stop_gradient(base_op))

    def body(leaves, d_loc):
        base = jax.tree_util.tree_unflatten(treedef, leaves)
        i0 = jax.lax.axis_index(axes) * n_loc
        rows_idx = i0 + jnp.arange(n_loc)

        def pivot_step(j, carry):
            L, d, picked = carry
            d_masked = jnp.where(picked, -jnp.inf, d)
            vals = jax.lax.all_gather(jnp.max(d_masked), axes)  # (shards,)
            args = jax.lax.all_gather(jnp.argmax(d_masked), axes)
            s = jnp.argmax(vals)
            piv = args[s] + s * n_loc  # global pivot row
            dpiv = jnp.clip(vals[s], 0.0)
            ok = dpiv > jitter
            sqrt_piv = jnp.sqrt(jnp.where(ok, dpiv, 1.0))

            # K[piv, local rows]: the row is recomputed replicated (cheap,
            # matmul-shaped), then sliced to this shard's block
            row = jax.lax.dynamic_slice_in_dim(
                base.row(piv).astype(dtype), i0, n_loc
            )
            # L[piv] lives on exactly one shard → masked psum broadcast
            owns = (piv >= i0) & (piv < i0 + n_loc)
            L_piv = jax.lax.psum(
                jnp.where(owns, L[jnp.clip(piv - i0, 0, n_loc - 1)], 0.0), axes
            )

            resid = row - L @ L_piv
            col = resid / sqrt_piv
            col = jnp.where(picked, 0.0, col)
            col = jnp.where(rows_idx == piv, sqrt_piv, col)
            col = jnp.where(ok, col, 0.0)

            L = L.at[:, j].set(col)
            d = d - col * col
            picked = picked | (rows_idx == piv)
            return (L, d, picked)

        L0 = jnp.zeros((n_loc, rank), dtype)
        picked0 = jnp.zeros((n_loc,), bool)
        L, _, _ = jax.lax.fori_loop(
            0, rank, pivot_step, (L0, d_loc.astype(dtype), picked0)
        )
        return L

    return unchecked_shard_map(
        body,
        mesh,
        in_specs=(tuple(P() for _ in leaves), P(axes)),
        out_specs=P(axes, None),
    )(tuple(leaves), diag)
