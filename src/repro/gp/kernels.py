"""Stationary kernels (RBF, Matérn family) + the KernelOperator.

The KernelOperator is the "exact GP" blackbox matmul (paper §4): it exposes
``(K_XX)·M`` without committing to a materialization strategy:

  * ``dense``   — materialize K once (small n; what the GPU paper does)
  * ``blocked`` — row-block streaming: each block of K is formed, used and
                  discarded (O(b·n) live memory) — the XLA analogue of the
                  fused Pallas kernel, and the form that row-shards across a
                  mesh (see ``repro/core/distributed.py``)
  * ``pallas``  — the fused VMEM-tiled TPU kernel (repro/kernels/kernel_matmul)

All three are numerically interchangeable; tests assert it.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.linear_operator import (
    LinearOperator,
    _mixed_matmul,
    _register,
    _xla_panel_matmul,
    static_field,
)
from repro.core.precision import is_reduced, normalize_compute_dtype


def sq_dist(X1: jax.Array, X2: jax.Array) -> jax.Array:
    """Pairwise squared euclidean distances, numerically clipped at 0."""
    n1 = jnp.sum(X1 * X1, axis=-1)
    n2 = jnp.sum(X2 * X2, axis=-1)
    d2 = n1[:, None] + n2[None, :] - 2.0 * (X1 @ X2.T)
    return jnp.clip(d2, 0.0)


@_register
@dataclasses.dataclass(frozen=True)
class RBFKernel:
    """k(x, x') = s · exp(−‖x−x'‖² / 2ℓ²)  (ARD when ℓ is a vector)."""

    lengthscale: jax.Array
    outputscale: jax.Array

    def __call__(self, X1, X2):
        d2 = sq_dist(X1 / self.lengthscale, X2 / self.lengthscale)
        return self.outputscale * jnp.exp(-0.5 * d2)

    def diag(self, X):
        return jnp.full((X.shape[0],), 1.0, X.dtype) * self.outputscale


@_register
@dataclasses.dataclass(frozen=True)
class MaternKernel:
    """Matérn-ν for ν ∈ {0.5, 1.5, 2.5} (paper experiments use 5/2)."""

    lengthscale: jax.Array
    outputscale: jax.Array
    nu: float = static_field(default=2.5)

    def __call__(self, X1, X2):
        d = jnp.sqrt(sq_dist(X1 / self.lengthscale, X2 / self.lengthscale) + 1e-20)
        if self.nu == 0.5:
            k = jnp.exp(-d)
        elif self.nu == 1.5:
            a = jnp.sqrt(3.0) * d
            k = (1.0 + a) * jnp.exp(-a)
        elif self.nu == 2.5:
            a = jnp.sqrt(5.0) * d
            k = (1.0 + a + a * a / 3.0) * jnp.exp(-a)
        else:  # pragma: no cover
            raise ValueError(f"unsupported nu={self.nu}")
        return self.outputscale * k

    def diag(self, X):
        return jnp.full((X.shape[0],), 1.0, X.dtype) * self.outputscale


@_register
@dataclasses.dataclass(frozen=True)
class DeepKernel:
    """k(g(x), g(x')) — deep kernel learning (paper §6 SKI+DKL experiments).

    ``feature_fn(params, X)`` is any JAX feature extractor (an MLP, or a
    full LM backbone via repro.gp.dkl); gradients flow into its params
    through the BBMM custom VJP like any other hyperparameter.
    """

    base: RBFKernel | MaternKernel
    net_params: any
    feature_fn: callable = static_field(default=None)

    def __call__(self, X1, X2):
        Z1 = self.feature_fn(self.net_params, X1)
        Z2 = self.feature_fn(self.net_params, X2)
        return self.base(Z1, Z2)

    def diag(self, X):
        return self.base.diag(X)


@jax.custom_vjp
def _pallas_matmul(op, M):
    """K(X, X) @ M through the Pallas kernel, with hand-wired gradients.

    ``op`` is a ``KernelOperator(mode="pallas")`` or its prepared form; the
    primal is its ``_pallas_forward`` (one ``pallas_call``).  ``pallas_call``
    has no usable JVP rule (lowering it dies on ``assert env.grid_context
    is not None``, compiled for TPU and interpreted alike), so the VJP
    differentiates the checkpointed XLA panel stream of the same product
    instead: one (panel_rows × n) kernel slab live at a time, never the
    dense K — the same seam as the partitioned path's
    ``_partitioned_matmul``."""
    return op._pallas_forward(M)


def _pallas_matmul_fwd(op, M):
    return op._pallas_forward(M), (op, M)


def _pallas_matmul_bwd(res, ct):
    from repro.kernels.kernel_matmul.ops import choose_panel_rows

    op, M = res
    p = choose_panel_rows(op.X.shape[0])

    def ref(kernel, X, m):
        return _xla_panel_matmul(kernel, X, X, m, p, compute_dtype=op.compute_dtype)

    _, vjp = jax.vjp(ref, op.kernel, op.X, M)
    kern_bar, X_bar, M_bar = vjp(ct)
    # a prepared operator's pre-scaled Xs is a pure function of
    # (kernel.lengthscale, X), both already accounted for: zero cotangent
    extra = {"Xs": jnp.zeros_like(op.Xs)} if hasattr(op, "Xs") else {}
    if getattr(op, "split", None) is not None:  # ... and so are its packed splits
        extra["split"] = jax.tree_util.tree_map(jnp.zeros_like, op.split)
    return dataclasses.replace(op, kernel=kern_bar, X=X_bar, **extra), M_bar


_pallas_matmul.defvjp(_pallas_matmul_fwd, _pallas_matmul_bwd)


@_register
@dataclasses.dataclass(frozen=True)
class KernelOperator(LinearOperator):
    """Exact-GP kernel matrix K(X, X) as a lazy blackbox matmul.

    ``mode="pallas_sharded"`` row-partitions the fused Pallas kernel over the
    mesh axes in ``data_axes`` (mesh resolved from the live context or the
    explicit ``mesh`` field): each device holds one row band, and the only
    per-matmul collective is the all-gather of the RHS.

    ``compute_dtype`` ('float32' | 'bfloat16', or the 'highest'/'mixed'
    precision aliases) selects the MXU operand dtype of the heavy
    contractions — bf16 tiles with f32 accumulation for the pallas paths,
    the equivalent rounded-operand matmul for the dense and blocked modes;
    accumulation, masking and the output stay f32 (see
    ``repro.core.precision``)."""

    kernel: object
    X: jax.Array  # (n, d)
    # dense | blocked | pallas | pallas_sharded | pallas_partitioned
    mode: str = static_field(default="dense")
    block_size: int = static_field(default=512)
    shard_rows: bool = static_field(default=False)  # annotate row sharding
    data_axes: tuple = static_field(default=("data",))  # sharded row axes
    mesh: object = static_field(default=None)  # explicit mesh (else live context)
    compute_dtype: str = static_field(default="float32")
    # pallas_partitioned knobs (see core.PartitionedKernelOperator):
    panel_rows: int = static_field(default=0)  # 0 → budget auto-chooser
    panel_budget_bytes: int = static_field(default=0)  # 0 → ops default
    panel_backend: str = static_field(default="auto")  # auto | pallas | xla

    @property
    def shape(self):
        n = self.X.shape[0]
        return (n, n)

    @property
    def dtype(self):
        return self.X.dtype

    def matmul(self, M):
        squeeze = M.ndim == 1
        if squeeze:
            M = M[:, None]
        if self.mode == "dense":
            K = self.kernel(self.X, self.X)
            out = _mixed_matmul(K, M) if is_reduced(self.compute_dtype) else K @ M
        elif self.mode == "blocked":
            out = self._blocked_matmul(M)
        elif self.mode == "pallas":
            out = _pallas_matmul(self, M)
        elif self.mode == "pallas_sharded":
            from repro.kernels.kernel_matmul.ops import sharded_kernel_matmul

            out = sharded_kernel_matmul(
                self.kernel, self.X, M, self._mesh(), self.data_axes,
                compute_dtype=self.compute_dtype,
            )
        elif self.mode == "pallas_partitioned":
            out = self._partitioned().matmul(M)
        else:  # pragma: no cover
            raise ValueError(self.mode)
        if self.shard_rows:
            from jax.sharding import PartitionSpec as P

            out = jax.lax.with_sharding_constraint(out, P(("pod", "data"), None))
        return out[:, 0] if squeeze else out

    def _pallas_forward(self, M):
        from repro.kernels.kernel_matmul.ops import kernel_matmul

        return kernel_matmul(self.kernel, self.X, M, self.compute_dtype)

    def _mesh(self):
        if self.mesh is not None:
            return self.mesh
        from repro.distributed.sharding import current_mesh

        mesh = current_mesh()
        if mesh is None:
            raise ValueError("pallas_sharded needs a mesh (field or live context)")
        return mesh

    def prepare(self):
        """Hoist the lengthscale pre-scaling out of the CG loop: returns an
        operator whose per-iteration matmul consumes the already-scaled X
        (single-device and sharded pallas modes).  Under a bf16
        ``compute_dtype`` the pre-scaled X is *stored* in bf16 — half the
        HBM footprint / gather payload for the whole solve; under f32,
        ``mode="pallas"`` also packs X's bf16 splits here, once per solve
        (``ops.pack_split_operands``).

        ``mode="pallas_partitioned"`` prepares into the streaming
        :class:`repro.core.PartitionedKernelOperator` — K is never
        materialized; its matmul runs one (panel_rows × n) row-panel at a
        time (see the class docstring for backend/sharding semantics)."""
        if self.mode == "pallas_partitioned":
            return self._partitioned().prepare()
        if self.mode not in ("pallas", "pallas_sharded"):
            return self
        from repro.kernels.kernel_matmul.ops import (
            _stationary_kernel_type,
            pack_split_operands,
            prescale_inputs,
        )

        Xs = prescale_inputs(self.X, self.kernel.lengthscale, self.compute_dtype)
        if self.mode == "pallas":
            cls = PreparedPallasKernelOperator
            # the f32 launch's packed bf16 splits of X, once per solve; no
            # gradient flows through them (the custom VJP differentiates
            # the XLA panel stream from kernel and X)
            extra = {} if is_reduced(self.compute_dtype) else {
                "split": pack_split_operands(*[jax.lax.stop_gradient(Xs)] * 2)
            }
        else:
            cls = PreparedShardedPallasKernelOperator
            extra = {"data_axes": self.data_axes, "mesh": self._mesh()}
        return cls(
            kernel=self.kernel,
            X=self.X,
            Xs=Xs,
            kernel_type=_stationary_kernel_type(self.kernel),
            compute_dtype=self.compute_dtype,
            **extra,
        )

    def with_compute_dtype(self, compute_dtype):
        return dataclasses.replace(
            self, compute_dtype=normalize_compute_dtype(compute_dtype)
        )

    def _partitioned(self):
        """The streaming operator behind ``mode="pallas_partitioned"``."""
        from repro.core.linear_operator import PartitionedKernelOperator

        return PartitionedKernelOperator(
            kernel=self.kernel,
            X=self.X,
            panel_rows=self.panel_rows,
            panel_budget_bytes=self.panel_budget_bytes,
            backend=self.panel_backend,
            data_axes=self.data_axes,
            mesh=self.mesh,
            compute_dtype=self.compute_dtype,
        )

    def fused_cg_step_fn(self, sigma2=None):
        """Fused CG capability: pallas modes delegate to their prepared form
        (the engine prepares before the loop anyway); dense/blocked keep the
        unfused fallback; the partitioned mode runs the PANEL-fused step —
        one fused launch per streamed row-panel per iteration, reductions
        carried across the panel loop (see
        ``PartitionedKernelOperator.fused_cg_step_fn``)."""
        if self.mode == "pallas_partitioned":
            return self._partitioned().fused_cg_step_fn(sigma2=sigma2)
        if self.mode not in ("pallas", "pallas_sharded"):
            return None
        return self.prepare().fused_cg_step_fn(sigma2=sigma2)

    def _blocked_matmul(self, M):
        n = self.X.shape[0]
        b = min(self.block_size, n)
        pad = (-n) % b
        Xp = jnp.pad(self.X, ((0, pad), (0, 0)))
        blocks = Xp.reshape(-1, b, self.X.shape[1])
        reduced = is_reduced(self.compute_dtype)

        def one_block(Xb):
            tile = self.kernel(Xb, self.X)  # (b, n)
            return _mixed_matmul(tile, M) if reduced else tile @ M  # (b, t)

        out = jax.lax.map(one_block, blocks).reshape(-1, M.shape[1])
        return out[:n]

    def row(self, i):
        return self.kernel(self.X[i][None, :], self.X)[0]

    def diagonal(self):
        return self.kernel.diag(self.X)


@_register
@dataclasses.dataclass(frozen=True)
class PreparedPallasKernelOperator(LinearOperator):
    """KernelOperator(mode='pallas') after ``prepare()``: X is already
    divided by the (possibly ARD) lengthscale, so the CG loop's
    per-iteration matmul does no redundant pre-scaling work."""

    kernel: object  # original kernel (row/diagonal accessors, outputscale)
    X: jax.Array  # (n, d) original inputs (row/diagonal accessors)
    Xs: jax.Array  # (n, d) pre-scaled (stored at compute_dtype)
    kernel_type: str = static_field(default="rbf")
    compute_dtype: str = static_field(default="float32")
    split: object = None  # ops.SplitOperands of Xs, prepared under "float32"

    @property
    def shape(self):
        n = self.X.shape[0]
        return (n, n)

    @property
    def dtype(self):
        return self.X.dtype

    def matmul(self, M):
        return _pallas_matmul(self, M)

    def _pallas_forward(self, M):
        from repro.kernels.kernel_matmul.ops import (
            fused_kernel_matmul_prescaled,
            split_kernel_matmul,
        )

        if self.split is not None and not is_reduced(self.compute_dtype):
            return split_kernel_matmul(
                self.split, M, self.kernel.outputscale, jnp.float32(0.0),
                kernel_type=self.kernel_type,
            )
        return fused_kernel_matmul_prescaled(
            self.Xs,
            self.Xs,
            M,
            self.kernel.outputscale,
            jnp.float32(0.0),
            kernel_type=self.kernel_type,
            compute_dtype=self.compute_dtype,
        )

    def with_compute_dtype(self, compute_dtype):
        # Xs keeps its stored dtype (a prepared bf16 Xs cannot regain f32
        # bits); the kernel casts operands to the requested compute_dtype
        from repro.core.precision import normalize_compute_dtype

        return dataclasses.replace(
            self, compute_dtype=normalize_compute_dtype(compute_dtype)
        )

    def fused_cg_step_fn(self, sigma2=None):
        """One-launch CG iteration: V = (K+σ²I)·D plus the state updates and
        the dᵀV/rᵀr/rᵀV/vᵀV reductions, all inside the Pallas sweep (see
        ``repro.kernels.kernel_matmul.ops.fused_cg_step_prescaled``)."""
        from repro.kernels.kernel_matmul.ops import fused_cg_step_prescaled, lane_aligned

        s2 = jnp.float32(0.0) if sigma2 is None else jnp.asarray(sigma2)
        if s2.ndim:
            return None
        Xs, outputscale = lane_aligned(self.Xs), self.kernel.outputscale
        kernel_type, compute_dtype = self.kernel_type, self.compute_dtype

        def step(U, R, D, V, alpha, beta, gamma):
            return fused_cg_step_prescaled(
                Xs, U, R, D, V, alpha, beta, gamma, outputscale, s2,
                kernel_type=kernel_type, compute_dtype=compute_dtype,
            )

        return step

    def row(self, i):
        return self.kernel(self.X[i][None, :], self.X)[0]

    def diagonal(self):
        return self.kernel.diag(self.X)


@_register
@dataclasses.dataclass(frozen=True)
class PreparedShardedPallasKernelOperator(LinearOperator):
    """KernelOperator(mode='pallas_sharded') after ``prepare()``: pre-scaled
    X and a resolved mesh, so the CG loop's per-iteration matmul is just the
    shard_map'd Pallas call (one RHS all-gather, no redundant pre-scaling)."""

    kernel: object
    X: jax.Array
    Xs: jax.Array  # (n, d) pre-scaled, replicated
    kernel_type: str = static_field(default="rbf")
    data_axes: tuple = static_field(default=("data",))
    mesh: object = static_field(default=None)
    compute_dtype: str = static_field(default="float32")

    @property
    def shape(self):
        n = self.X.shape[0]
        return (n, n)

    @property
    def dtype(self):
        return self.X.dtype

    def matmul(self, M):
        from repro.kernels.kernel_matmul.ops import sharded_kernel_matmul_prescaled

        return sharded_kernel_matmul_prescaled(
            self.Xs,
            M,
            self.kernel.outputscale,
            self.mesh,
            self.data_axes,
            kernel_type=self.kernel_type,
            compute_dtype=self.compute_dtype,
        )

    def with_compute_dtype(self, compute_dtype):
        return dataclasses.replace(
            self, compute_dtype=normalize_compute_dtype(compute_dtype)
        )

    def fused_cg_step_fn(self, sigma2=None):
        """Row-partitioned one-launch CG iteration: each device fuses its row
        band's updates + matmul + partial reductions, psum'd to O(t) — see
        ``ops.sharded_fused_cg_step_prescaled``."""
        from repro.kernels.kernel_matmul.ops import (
            lane_aligned,
            sharded_fused_cg_step_prescaled,
        )

        s2 = jnp.float32(0.0) if sigma2 is None else jnp.asarray(sigma2)
        if s2.ndim:
            return None
        Xs, outputscale = lane_aligned(self.Xs), self.kernel.outputscale
        kernel_type, compute_dtype = self.kernel_type, self.compute_dtype
        mesh, axes = self.mesh, self.data_axes

        def step(U, R, D, V, alpha, beta, gamma):
            return sharded_fused_cg_step_prescaled(
                Xs, U, R, D, V, alpha, beta, gamma, outputscale, s2, mesh, axes,
                kernel_type=kernel_type, compute_dtype=compute_dtype,
            )

        return step

    def row(self, i):
        return self.kernel(self.X[i][None, :], self.X)[0]

    def diagonal(self):
        return self.kernel.diag(self.X)


@_register
@dataclasses.dataclass(frozen=True)
class CrossKernelOperator:
    """k(X1, X2) rectangular block for predictions (not square — helper).

    ``compute_dtype`` routes the test-vs-train cross matmul through the
    same precision policy as the training operators (bf16 operands, f32
    accumulation under ``"bfloat16"``/``"mixed"``) — so a model trained at
    ``precision="mixed"`` predicts through a consistent reduced-precision
    contraction instead of silently upcasting at serving time."""

    kernel: object
    X1: jax.Array
    X2: jax.Array
    compute_dtype: str = static_field(default="float32")

    @property
    def shape(self):
        return (self.X1.shape[0], self.X2.shape[0])

    def to_dense(self):
        return self.kernel(self.X1, self.X2)

    def contract(self, K, M):
        """K @ M under this operator's precision policy, for a precomputed
        cross block K (e.g. ``to_dense()`` or its transpose) — lets serving
        paths evaluate the kernel block ONCE and reuse it for both the
        policy-consistent mean contraction and the variance expansion."""
        return _mixed_matmul(K, M) if is_reduced(self.compute_dtype) else K @ M

    def matmul(self, M):
        return self.contract(self.kernel(self.X1, self.X2), M)

    def rmatmul(self, M):
        return self.contract(self.kernel(self.X2, self.X1), M)

    def with_compute_dtype(self, compute_dtype):
        return dataclasses.replace(
            self, compute_dtype=normalize_compute_dtype(compute_dtype)
        )
