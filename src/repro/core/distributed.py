"""Distributed BBMM: row-block sharded kernel matmuls (beyond the paper).

The paper fills one GPU with a single big GEMM; here the same blackbox is
spread across a TPU pod.  Layout:

  * X (n, d): replicated (d is small; n·d ≪ HBM even at n = 2M)
  * M (n, t): row-sharded over the data axes
  * each chip owns rows [i₀:i₁) of K̂ and computes K(X_loc, ·) against
    column *chunks* of X so the live kernel tile is (n_loc × chunk) — the
    multi-chip analogue of the VMEM tiling in the Pallas kernel.

Collectives per matmul: ONE all-gather of M (n·t bytes) — O(n) communication
against O(n²/devices) compute, so arithmetic intensity grows linearly in n.
CG's inner products reduce over the row axis and become psums automatically
under pjit.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .linear_operator import LinearOperator, _register, static_field


def _local_block_matmul(kernel, X_local, X_full, M_full, chunk: int):
    """Σ_c K(X_local, X_full[c]) @ M_full[c] without materializing the row
    panel — scan over column chunks.  The body is rematerialized: kernel
    tiles are *recomputed* in the backward pass instead of saved (saving
    them would store O(n²/devices) — the exact thing BBMM avoids).

    The contraction runs at the inputs' dtype (bf16 tiles → full MXU rate)
    but always accumulates in f32."""
    n = X_full.shape[0]
    pad = (-n) % chunk
    Xp = jnp.pad(X_full, ((0, pad), (0, 0)))
    Mp = jnp.pad(M_full, ((0, pad), (0, 0)))
    Xc = Xp.reshape(-1, chunk, X_full.shape[1])
    Mc = Mp.reshape(-1, chunk, M_full.shape[1])
    tile_dtype = M_full.dtype

    @jax.checkpoint
    def body(acc, xm):
        Xb, Mb = xm
        tile = kernel(X_local, Xb).astype(tile_dtype)
        part = jax.lax.dot_general(
            tile, Mb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return acc + part, None

    init = jnp.zeros((X_local.shape[0], M_full.shape[1]), jnp.float32)
    out, _ = jax.lax.scan(body, init, (Xc, Mc))
    return out


@_register
@dataclasses.dataclass(frozen=True)
class ShardedKernelOperator(LinearOperator):
    """Row-block sharded exact-GP kernel operator (shard_map based).

    Use inside a ``jax.set_mesh`` scope. ``data_axes`` names the mesh axes
    that shard the n rows of M / K̂ (typically ("pod", "data") or their
    product with "model" — see the §Perf hillclimb).
    """

    kernel: object
    X: jax.Array  # (n, d) — replicated
    data_axes: tuple = static_field(default=("data",))
    chunk: int = static_field(default=8192)
    compute_dtype: str = static_field(default="float32")  # bf16 tiles → 2× MXU rate
    mesh: object = static_field(default=None)  # explicit mesh (else live context)

    @property
    def shape(self):
        n = self.X.shape[0]
        return (n, n)

    @property
    def dtype(self):
        return self.X.dtype

    def matmul(self, M):
        from repro.distributed.sharding import (
            current_mesh,
            mesh_axis_sizes,
            unchecked_shard_map,
        )

        squeeze = M.ndim == 1
        if squeeze:
            M = M[:, None]
        mesh = self.mesh if self.mesh is not None else current_mesh()
        sizes = mesh_axis_sizes(mesh)
        shards = 1
        for a in self.data_axes:
            shards *= sizes[a]
        axes = self.data_axes
        chunk = self.chunk
        # kernel hyperparameters enter as explicit (replicated) shard_map
        # operands — closure capture of traced values breaks vjp tracing
        kern_leaves, kern_def = jax.tree_util.tree_flatten(self.kernel)

        from .precision import is_reduced

        compute_dtype = jnp.bfloat16 if is_reduced(self.compute_dtype) else jnp.float32

        def body(kern_leaves, X_full, M_loc):
            kernel = jax.tree_util.tree_unflatten(kern_def, kern_leaves)
            if compute_dtype == jnp.bfloat16:
                # half-width tiles AND a half-width gather payload
                M_loc = M_loc.astype(jnp.bfloat16)
                X_full = X_full.astype(jnp.bfloat16)
            M_full = jax.lax.all_gather(M_loc, axes, axis=0, tiled=True)
            # rows owned by this shard
            idx = jax.lax.axis_index(axes)
            n_loc = X_full.shape[0] // shards
            X_loc = jax.lax.dynamic_slice_in_dim(X_full, idx * n_loc, n_loc, axis=0)
            out = _local_block_matmul(kernel, X_loc, X_full, M_full, chunk)
            return out.astype(jnp.float32)

        out = unchecked_shard_map(
            body,
            mesh,
            in_specs=(tuple(P() for _ in kern_leaves), P(None, None), P(axes, None)),
            out_specs=P(axes, None),
        )(tuple(kern_leaves), self.X, M)
        return out[:, 0] if squeeze else out

    def row(self, i):
        return self.kernel(self.X[i][None, :], self.X)[0]

    def diagonal(self):
        return self.kernel.diag(self.X)

    def with_compute_dtype(self, compute_dtype):
        from .precision import normalize_compute_dtype

        return dataclasses.replace(
            self, compute_dtype=normalize_compute_dtype(compute_dtype)
        )

    def fused_cg_step_fn(self, sigma2=None):
        """Sharded fused CG step: ONE shard_map region per iteration.

        Each device applies the pending (α, β, γ) updates to its own row
        band, computes its V band through the chunked local matmul of
        K̂ = K + σ²I, and contributes partial dᵀV/rᵀr/rᵀV/vᵀV reductions
        that are ``psum``'d — so the unfused path's replicated XLA passes
        over the full (n, t) state (and their per-pass collectives under
        pjit) collapse into one region with a 3-array gather + one O(t)
        psum."""
        from repro.distributed.sharding import mesh_axis_sizes, unchecked_shard_map

        s2 = jnp.float32(0.0) if sigma2 is None else jnp.asarray(sigma2)
        if s2.ndim:
            return None
        mesh = self.mesh
        if mesh is None:
            from repro.distributed.sharding import current_mesh

            mesh = current_mesh()
        if mesh is None:
            return None
        axes, chunk = self.data_axes, self.chunk
        sizes = mesh_axis_sizes(mesh)
        shards = 1
        for a in axes:
            shards *= sizes[a]
        n = self.X.shape[0]
        if n % shards != 0:
            return None  # uneven row bands: keep the unfused fallback
        kern_leaves, kern_def = jax.tree_util.tree_flatten(self.kernel)

        from .mbcg import xla_cg_step
        from .precision import is_reduced

        reduced = is_reduced(self.compute_dtype)

        def body(kern_leaves, X_full, s2, U, R, D, V, alpha, beta, gamma):
            kernel = jax.tree_util.tree_unflatten(kern_def, kern_leaves)

            def local_mm(D_loc):
                D_full = jax.lax.all_gather(D_loc, axes, axis=D_loc.ndim - 2, tiled=True)
                Xf = X_full
                if reduced:
                    # bf16 MXU tiles with f32 accumulation; the CG state and
                    # its gather stay f32 so the recurrence never loses bits
                    Xf = Xf.astype(jnp.bfloat16)
                    D_full = D_full.astype(jnp.bfloat16)
                idx = jax.lax.axis_index(axes)
                n_loc = n // shards
                X_loc = jax.lax.dynamic_slice_in_dim(Xf, idx * n_loc, n_loc, axis=0)
                return _local_block_matmul(kernel, X_loc, Xf, D_full, chunk) + s2 * D_loc

            # the canonical CGStepFn recurrence on this device's row band —
            # only the reductions need the cross-band psum
            U, R, D, V, red = xla_cg_step(local_mm)(U, R, D, V, alpha, beta, gamma)
            return U, R, D, V, jax.lax.psum(red, axes)

        def step(U, R, D, V, alpha, beta, gamma):
            state_spec = P(*([None] * (U.ndim - 2)), axes, None)
            rep = P(*([None] * (U.ndim - 1)))
            return unchecked_shard_map(
                body,
                mesh,
                in_specs=(
                    tuple(P() for _ in kern_leaves),
                    P(None, None),
                    P(),
                    state_spec,
                    state_spec,
                    state_spec,
                    state_spec,
                    rep,
                    rep,
                    rep,
                ),
                out_specs=(
                    state_spec,
                    state_spec,
                    state_spec,
                    state_spec,
                    (rep, rep, rep, rep),
                ),
            )(tuple(kern_leaves), self.X, s2, U, R, D, V, alpha, beta, gamma)

        return step


def replicated(x):
    """Convenience NamedSharding-free replication constraint."""
    return jax.lax.with_sharding_constraint(x, P())


def row_sharded(x, axes=("data",)):
    return jax.lax.with_sharding_constraint(x, P(axes, *([None] * (x.ndim - 1))))
