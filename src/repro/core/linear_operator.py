"""LinearOperator: the blackbox matrix abstraction at the heart of BBMM.

Every GP model in the paper (§5) reduces to "a routine for matrix-matrix
multiplication with the kernel matrix".  A :class:`LinearOperator` packages
that routine together with the handful of cheap auxiliary accessors the
inference engine needs:

  * ``matmul(M)``   — the blackbox ``K @ M``       (drives mBCG)
  * ``diagonal()``  — ``diag(K)``                  (drives pivoted Cholesky)
  * ``row(i)``      — ``K[i, :]``                  (drives pivoted Cholesky)

All operators are registered JAX pytrees, so they flow through ``jit`` /
``grad`` / ``scan`` and their *array leaves are differentiable* — the
derivative matmul ``(dK/dθ) @ M`` the paper asks the user for is obtained
for free from ``jax.vjp`` of ``matmul``.
"""

from __future__ import annotations

import dataclasses
import threading
import warnings
from contextlib import contextmanager
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .precision import is_reduced, normalize_compute_dtype


def _mixed_matmul(A, B):
    """A @ B with bf16 MXU operands and f32 accumulation — the reduced-
    precision contraction every mixed-policy operator shares."""
    return jnp.matmul(
        A.astype(jnp.bfloat16),
        B.astype(jnp.bfloat16),
        preferred_element_type=jnp.float32,
    )


def _register(cls):
    """Register a dataclass operator as a pytree (fields with metadata
    ``static=True`` become aux data)."""
    fields = dataclasses.fields(cls)
    dyn = [f.name for f in fields if not f.metadata.get("static", False)]
    sta = [f.name for f in fields if f.metadata.get("static", False)]

    def flatten(op):
        return tuple(getattr(op, n) for n in dyn), tuple(getattr(op, n) for n in sta)

    def unflatten(aux, children):
        kwargs = dict(zip(dyn, children))
        kwargs.update(dict(zip(sta, aux)))
        return cls(**kwargs)

    jax.tree_util.register_pytree_node(cls, flatten, unflatten)
    return cls


def static_field(**kw):
    return dataclasses.field(metadata={"static": True}, **kw)


class LinearOperator:
    """Abstract symmetric (PSD in GP usage) linear operator of shape (n, n)."""

    # -- required ---------------------------------------------------------
    @property
    def shape(self) -> tuple[int, int]:
        raise NotImplementedError

    def matmul(self, M: jax.Array) -> jax.Array:
        """K @ M for M of shape (n, t) (or (n,) vector)."""
        raise NotImplementedError

    # -- optional (defaults via matmul; O(n) columns = slow, override) ----
    def diagonal(self) -> jax.Array:
        n = self.shape[0]
        return jax.vmap(lambda i: self.row(i)[i])(jnp.arange(n))

    def row(self, i) -> jax.Array:
        n = self.shape[0]
        e = jnp.zeros((n,), self.dtype).at[i].set(1.0)
        return self.matmul(e[:, None])[:, 0]

    def to_dense(self) -> jax.Array:
        return self.matmul(jnp.eye(self.shape[0], dtype=self.dtype))

    @property
    def dtype(self):
        return jnp.float32

    # -- solver preparation ------------------------------------------------
    def prepare(self) -> "LinearOperator":
        """Return an equivalent operator with per-solve work hoisted.

        The inference engine calls this ONCE before entering the CG loop, so
        anything done here (lengthscale pre-scaling, padding, layout changes)
        is paid once per solve instead of once per iteration.  Default: no-op.
        Wrappers recurse into their children."""
        return self

    # -- fused CG capability ----------------------------------------------
    def fused_cg_step_fn(self, sigma2=None):
        """Return a :data:`repro.core.mbcg.CGStepFn` executing one whole CG
        iteration of K̂ = self + σ²I as a single fused launch, or None.

        Default: None — generic operators keep the *unfused* mBCG loop (the
        engine falls back transparently).  The Pallas kernel-matmul family
        overrides this: their kernels apply the pending CG state updates,
        compute V = K̂·D and accumulate the per-column reductions inside one
        grid sweep (see ``repro.kernels.kernel_matmul``).  ``sigma2`` is the
        added diagonal folded into the kernel tile —
        :class:`AddedDiagOperator` threads its noise through here, which is
        why the capability takes σ² instead of requiring a wrapper-aware
        kernel."""
        return None

    # -- precision policy --------------------------------------------------
    def with_compute_dtype(self, compute_dtype) -> "LinearOperator":
        """Return an equivalent operator whose matmul runs its heavy
        contractions at ``compute_dtype`` ('float32' | 'bfloat16', or the
        'highest'/'mixed' aliases), always accumulating in f32.

        Default: no-op — operators whose matmul has no reduced-precision
        formulation worth taking (Toeplitz/FFT, diagonal, blackbox
        callables) stay at full precision under the mixed policy, which is
        always *correct*, just not faster.  Wrappers recurse into their
        children; σ² diagonals and scalar scales stay f32."""
        normalize_compute_dtype(compute_dtype)  # validate even on the no-op
        return self

    # -- algebra ----------------------------------------------------------
    def __add__(self, other):
        if isinstance(other, LinearOperator):
            return SumOperator((self, other))
        raise TypeError(other)

    def __mul__(self, scalar):
        return ScaledOperator(self, jnp.asarray(scalar, self.dtype))

    __rmul__ = __mul__

    def add_diagonal(self, sigma2) -> "AddedDiagOperator":
        return AddedDiagOperator(self, jnp.asarray(sigma2, self.dtype))

    def __call__(self, M):
        return self.matmul(M)


@_register
@dataclasses.dataclass(frozen=True)
class DenseOperator(LinearOperator):
    """Explicit symmetric matrix.

    ``compute_dtype="bfloat16"`` rounds both matmul operands to bf16 and
    accumulates in f32 — on TPU the 2× MXU-rate path, everywhere else the
    faithful emulation of it that the mixed-precision CG tests and the
    benchmark tolerance study run against."""

    matrix: jax.Array
    compute_dtype: str = static_field(default="float32")

    @property
    def shape(self):
        return self.matrix.shape

    @property
    def dtype(self):
        return self.matrix.dtype

    def matmul(self, M):
        if is_reduced(self.compute_dtype):
            return _mixed_matmul(self.matrix, M)
        return self.matrix @ M

    def with_compute_dtype(self, compute_dtype):
        return dataclasses.replace(
            self, compute_dtype=normalize_compute_dtype(compute_dtype)
        )

    def diagonal(self):
        return jnp.diagonal(self.matrix)

    def row(self, i):
        return self.matrix[i]

    def to_dense(self):
        return self.matrix


@_register
@dataclasses.dataclass(frozen=True)
class DiagOperator(LinearOperator):
    diag: jax.Array

    @property
    def shape(self):
        n = self.diag.shape[0]
        return (n, n)

    @property
    def dtype(self):
        return self.diag.dtype

    def matmul(self, M):
        if M.ndim == 1:
            return self.diag * M
        return self.diag[:, None] * M

    def diagonal(self):
        return self.diag

    def row(self, i):
        return jnp.zeros_like(self.diag).at[i].set(self.diag[i])

    def to_dense(self):
        return jnp.diag(self.diag)


@_register
@dataclasses.dataclass(frozen=True)
class ScaledOperator(LinearOperator):
    base: LinearOperator
    scale: jax.Array

    @property
    def shape(self):
        return self.base.shape

    @property
    def dtype(self):
        return self.base.dtype

    def matmul(self, M):
        return self.scale * self.base.matmul(M)

    def diagonal(self):
        return self.scale * self.base.diagonal()

    def row(self, i):
        return self.scale * self.base.row(i)

    def prepare(self):
        return ScaledOperator(self.base.prepare(), self.scale)

    def with_compute_dtype(self, compute_dtype):
        return ScaledOperator(self.base.with_compute_dtype(compute_dtype), self.scale)


@_register
@dataclasses.dataclass(frozen=True)
class SumOperator(LinearOperator):
    """K1 + K2 + ... — compositional kernels (paper §5 'Compositions')."""

    ops: tuple

    @property
    def shape(self):
        return self.ops[0].shape

    @property
    def dtype(self):
        return self.ops[0].dtype

    def matmul(self, M):
        out = self.ops[0].matmul(M)
        for op in self.ops[1:]:
            out = out + op.matmul(M)
        return out

    def diagonal(self):
        out = self.ops[0].diagonal()
        for op in self.ops[1:]:
            out = out + op.diagonal()
        return out

    def row(self, i):
        out = self.ops[0].row(i)
        for op in self.ops[1:]:
            out = out + op.row(i)
        return out

    def prepare(self):
        return SumOperator(tuple(op.prepare() for op in self.ops))

    def with_compute_dtype(self, compute_dtype):
        return SumOperator(tuple(op.with_compute_dtype(compute_dtype) for op in self.ops))


@_register
@dataclasses.dataclass(frozen=True)
class AddedDiagOperator(LinearOperator):
    """K̂ = K + σ²·I — the paper's hatted matrix.

    Kept as its own node (rather than SumOperator) because the inference
    engine builds the pivoted-Cholesky preconditioner from ``base`` and the
    noise separately (P̂ = L_k L_kᵀ + σ²I).
    """

    base: LinearOperator
    sigma2: jax.Array  # scalar, or (b,) for a batch of noise levels

    @property
    def shape(self):
        return self.base.shape

    @property
    def dtype(self):
        return self.base.dtype

    def _s2(self, extra_dims):
        s2 = jnp.asarray(self.sigma2)
        return s2.reshape(s2.shape + (1,) * extra_dims) if s2.ndim else s2

    def matmul(self, M):
        return self.base.matmul(M) + self._s2(2 if M.ndim > 1 else 1) * M

    def diagonal(self):
        return self.base.diagonal() + self._s2(1)

    def row(self, i):
        r = self.base.row(i)
        return r.at[i].add(self.sigma2)

    def to_dense(self):
        # structural materialization (base dense + σ²I) rather than the
        # matmul-against-identity default: the degradation ladder's terminal
        # dense-Cholesky rung must stay independent of the blackbox matmul
        # it is recovering from
        dense = self.base.to_dense()
        eye = jnp.eye(dense.shape[-1], dtype=dense.dtype)
        return dense + self._s2(2) * eye

    def prepare(self):
        return AddedDiagOperator(self.base.prepare(), self.sigma2)

    def with_compute_dtype(self, compute_dtype):
        # σ²·M stays f32 — only the base kernel matmul takes reduced precision
        return AddedDiagOperator(self.base.with_compute_dtype(compute_dtype), self.sigma2)

    def fused_cg_step_fn(self, sigma2=None):
        # fold this diagonal into the base kernel's σ² tile term (the Pallas
        # kernel emits it at global row == col, so the fused step IS K̂·D)
        s2 = jnp.asarray(self.sigma2)
        if s2.ndim:
            # batched noise: no scalar σ² tile — unfused fallback
            if self.base.fused_cg_step_fn.__func__ is not (
                LinearOperator.fused_cg_step_fn
            ):
                _warn_once_per_op(
                    self,
                    "added_diag_batched_sigma2",
                    "fuse_cg=True with batched (per-model) noise: the fused "
                    "kernel folds one scalar σ² into its diagonal tile, so "
                    "batched σ² runs the unfused mBCG loop instead.",
                )
            return None
        if sigma2 is not None:
            s2 = s2 + sigma2
        return self.base.fused_cg_step_fn(sigma2=s2)


@_register
@dataclasses.dataclass(frozen=True)
class LowRankRootOperator(LinearOperator):
    """R @ Rᵀ for a tall-skinny root R (n × m).

    This is the SoR/SGPR building block: K ≈ (K_XU L⁻ᵀ)(K_XU L⁻ᵀ)ᵀ with
    L = chol(K_UU) — an O(tnm) matmul (paper §5, SGPR).
    """

    root: jax.Array  # (n, m)
    compute_dtype: str = static_field(default="float32")

    @property
    def shape(self):
        n = self.root.shape[0]
        return (n, n)

    @property
    def dtype(self):
        return self.root.dtype

    def matmul(self, M):
        if is_reduced(self.compute_dtype):
            # both O(tnm) contractions at bf16, each accumulating in f32
            return _mixed_matmul(self.root, _mixed_matmul(self.root.T, M))
        return self.root @ (self.root.T @ M)

    def with_compute_dtype(self, compute_dtype):
        return dataclasses.replace(
            self, compute_dtype=normalize_compute_dtype(compute_dtype)
        )

    def diagonal(self):
        return jnp.sum(self.root * self.root, axis=-1)

    def row(self, i):
        return self.root @ self.root[i]


@_register
@dataclasses.dataclass(frozen=True)
class ToeplitzOperator(LinearOperator):
    """Symmetric Toeplitz matrix defined by its first column (m,).

    Matmul via circulant embedding + FFT: O(t·m log m) — the SKI/KISS-GP
    K_UU on a regular grid (paper §5).
    """

    column: jax.Array  # (m,)

    @property
    def shape(self):
        m = self.column.shape[0]
        return (m, m)

    @property
    def dtype(self):
        return self.column.dtype

    def matmul(self, M):
        squeeze = M.ndim == 1
        if squeeze:
            M = M[:, None]
        m = self.column.shape[0]
        # circulant embedding of size 2m: [c0 c1 .. c_{m-1} * c_{m-1} .. c1]
        c = jnp.concatenate(
            [self.column, jnp.zeros((1,), self.column.dtype), self.column[1:][::-1]]
        )
        fc = jnp.fft.rfft(c)
        fM = jnp.fft.rfft(M.astype(jnp.float32), n=2 * m, axis=0)
        out = jnp.fft.irfft(fc[:, None] * fM, n=2 * m, axis=0)[:m]
        out = out.astype(M.dtype)
        return out[:, 0] if squeeze else out

    def diagonal(self):
        return jnp.full((self.column.shape[0],), self.column[0], self.column.dtype)

    def row(self, i):
        m = self.column.shape[0]
        idx = jnp.abs(jnp.arange(m) - i)
        return self.column[idx]

    def to_dense(self):
        m = self.column.shape[0]
        idx = jnp.abs(jnp.arange(m)[:, None] - jnp.arange(m)[None, :])
        return self.column[idx]


@_register
@dataclasses.dataclass(frozen=True)
class InterpolatedOperator(LinearOperator):
    """W K_base Wᵀ with sparse interpolation W (n × m, q nonzeros per row).

    W is stored as (indices, values) of shape (n, q).  This is SKI:
    ``matmul`` costs O(t·n·q) for the interpolations plus one base matmul —
    with a Toeplitz base that is the paper's O(t·n + t·m log m).
    """

    indices: jax.Array  # (n, q) int32, column index of each nonzero
    values: jax.Array  # (n, q) float
    base: LinearOperator  # (m, m)

    @property
    def shape(self):
        n = self.indices.shape[0]
        return (n, n)

    @property
    def dtype(self):
        return self.values.dtype

    def _Wt_matmul(self, M):
        """Wᵀ @ M : (m, t) — scatter-add of weighted rows."""
        m = self.base.shape[0]
        if M.ndim == 1:
            M = M[:, None]
        # contributions: values[n, q] * M[n, t] scattered to rows indices[n, q]
        contrib = self.values[..., None] * M[:, None, :]  # (n, q, t)
        flat_idx = self.indices.reshape(-1)  # (n*q,)
        flat_con = contrib.reshape(-1, M.shape[-1])  # (n*q, t)
        return jax.ops.segment_sum(flat_con, flat_idx, num_segments=m)

    def _W_matmul(self, V):
        """W @ V : (n, t) — gather of weighted rows."""
        if V.ndim == 1:
            V = V[:, None]
        gathered = V[self.indices]  # (n, q, t)
        return jnp.sum(self.values[..., None] * gathered, axis=1)

    def matmul(self, M):
        squeeze = M.ndim == 1
        out = self._W_matmul(self.base.matmul(self._Wt_matmul(M)))
        return out[:, 0] if squeeze else out

    def row(self, i):
        # (W K Wᵀ)[i, :] = W @ (K @ w_i)
        m = self.base.shape[0]
        w_i = jnp.zeros((m,), self.dtype).at[self.indices[i]].add(self.values[i])
        return self._W_matmul(self.base.matmul(w_i[:, None]))[:, 0]

    def diagonal(self):
        # diag_i = w_i K w_iᵀ over the q×q sub-block of K
        sub = jax.vmap(
            lambda idx: jax.vmap(lambda a: jax.vmap(lambda b: self._base_entry(a, b))(idx))(idx)
        )(self.indices)  # (n, q, q)
        return jnp.einsum("nq,nqr,nr->n", self.values, sub, self.values)

    def _base_entry(self, a, b):
        return self.base.row(a)[b]

    def with_compute_dtype(self, compute_dtype):
        # the sparse W gather/scatter stays f32 (segment_sum accumulation);
        # only the base K_UU matmul is eligible for reduced precision
        return dataclasses.replace(self, base=self.base.with_compute_dtype(compute_dtype))


@_register
@dataclasses.dataclass(frozen=True)
class KroneckerOperator(LinearOperator):
    """K₁ ⊗ K₂ ⊗ … — multi-dimensional SKI grids (paper §5 / KISS-GP).

    matmul applies each factor along its own grid axis:
    O(t·m·Σmᵢ) instead of O(t·m²) for m = Πmᵢ.
    """

    factors: tuple  # of LinearOperators

    @property
    def shape(self):
        m = 1
        for f in self.factors:
            m *= f.shape[0]
        return (m, m)

    @property
    def dtype(self):
        return self.factors[0].dtype

    def matmul(self, M):
        squeeze = M.ndim == 1
        if squeeze:
            M = M[:, None]
        t = M.shape[-1]
        dims = [f.shape[0] for f in self.factors]
        out = M.reshape(*dims, t)
        # contract factor i along axis i
        for i, f in enumerate(self.factors):
            moved = jnp.moveaxis(out, i, 0)  # (m_i, ..., t)
            rest = moved.shape[1:]
            flat = moved.reshape(dims[i], -1)
            flat = f.matmul(flat)
            out = jnp.moveaxis(flat.reshape(dims[i], *rest), 0, i)
        out = out.reshape(-1, t)
        return out[:, 0] if squeeze else out

    def diagonal(self):
        d = self.factors[0].diagonal()
        for f in self.factors[1:]:
            d = jnp.outer(d, f.diagonal()).reshape(-1)
        return d

    def row(self, i):
        dims = [f.shape[0] for f in self.factors]
        rem = i
        # decompose i into per-factor indices (row-major)
        idxs = []
        for m in reversed(dims):
            idxs.append(rem % m)
            rem = rem // m
        idxs = idxs[::-1]
        r = self.factors[0].row(idxs[0])
        for f, j in zip(self.factors[1:], idxs[1:]):
            r = jnp.outer(r, f.row(j)).reshape(-1)
        return r

    def with_compute_dtype(self, compute_dtype):
        return KroneckerOperator(
            tuple(f.with_compute_dtype(compute_dtype) for f in self.factors)
        )


_FUSED_FALLBACK_WARNED: dict = {}


def _warn_once_per_op(op, key, message):
    """Warn once per operator *construction*, not once per solve.

    ``fused_cg_step_fn`` is probed on every engine solve, and the wrappers'
    ``prepare()``/``_partitioned()`` plumbing rebuilds fresh operator
    instances per probe — so a per-instance flag would still warn every
    solve of a training loop.  Instead the dedup token is the identity of
    the operator's array leaves: ``dataclasses.replace`` and the wrapper
    constructors reuse the same underlying arrays, so every re-prepared
    copy of one user-constructed operator maps to the same token, while a
    genuinely new operator (new parameter arrays) warns afresh.  Inside a
    ``jit`` trace the leaves are per-trace tracers, so each distinct
    compilation warns at most once — also the right granularity."""
    leaves = jax.tree_util.tree_leaves(op)
    token = (
        key,
        tuple(id(l) for l in leaves) if leaves else id(op),
        tuple(getattr(l, "shape", ()) for l in leaves),
    )
    if token in _FUSED_FALLBACK_WARNED:
        return
    if len(_FUSED_FALLBACK_WARNED) > 4096:
        _FUSED_FALLBACK_WARNED.clear()
    _FUSED_FALLBACK_WARNED[token] = True
    warnings.warn(message, stacklevel=4)


def _warn_unfused_kronecker(op):
    _warn_once_per_op(
        op,
        "kronecker_unfused",
        "fuse_cg=True requested on a Kronecker-structured operator: fusing the "
        "Kronecker CG step into one Pallas launch is a documented frontier "
        "(ROADMAP), not implemented — falling back to the unfused mBCG loop. "
        "The data-kernel matmul inside each iteration still runs the "
        "prepared/sharded Pallas path.",
    )


@_register
@dataclasses.dataclass(frozen=True)
class KroneckerKernelOperator(LinearOperator):
    """K_X ⊗ K_T — the multitask GP covariance over a complete task grid.

    Row layout is *data-major*: global row ``i·T + τ`` is (data point i,
    task τ), so ``(K_X ⊗ K_T)[iT+τ, jT+τ'] = K_X[i,j]·K_T[τ,τ']``.

    ``matmul`` is ONE data-kernel call per application: the (n·T, t) RHS is
    reshaped into an (n, T·t) block, pushed through ``data_op.matmul``
    (whatever its implementation — dense, blocked, Pallas, row-sharded
    Pallas; ``prepare``/``with_compute_dtype`` recurse, so lengthscale
    pre-scaling, batching, edge masking and bf16 tiles are all inherited),
    then contracted against the small dense (T, T) task kernel:
    O(t·(n²T + nT²)) instead of the naive O(t·n²T²).

    The task kernel stays an explicit f32 matrix (T is small — it is the
    learned B·Bᵀ + diag(v) of :class:`repro.gp.multitask.MultitaskGP`).
    """

    data_op: LinearOperator  # (n, n) — any data-kernel operator
    task: jax.Array  # (T, T) dense symmetric PSD task kernel

    @property
    def shape(self):
        nT = self.data_op.shape[0] * self.task.shape[0]
        return (nT, nT)

    @property
    def num_tasks(self) -> int:
        return self.task.shape[0]

    @property
    def dtype(self):
        return self.data_op.dtype

    def matmul(self, M):
        squeeze = M.ndim == 1
        if squeeze:
            M = M[:, None]
        T = self.task.shape[0]
        n = self.data_op.shape[0]
        t = M.shape[-1]
        batch = M.shape[:-2]
        block = M.reshape(*batch, n, T * t)  # row iT+τ → (i, τ·t + col)
        Y = self.data_op.matmul(block).reshape(*batch, n, T, t)
        out = jnp.einsum("st,...utc->...usc", self.task, Y)
        out = out.reshape(*batch, n * T, t)
        return out[..., 0] if squeeze else out

    def diagonal(self):
        return jnp.outer(self.data_op.diagonal(), jnp.diagonal(self.task)).reshape(-1)

    def row(self, i):
        T = self.task.shape[0]
        return jnp.outer(self.data_op.row(i // T), self.task[i % T]).reshape(-1)

    def prepare(self):
        return KroneckerKernelOperator(self.data_op.prepare(), self.task)

    def with_compute_dtype(self, compute_dtype):
        # the O(n²·Tt) data matmul takes the reduced policy; the tiny (T, T)
        # task contraction stays f32
        return KroneckerKernelOperator(
            self.data_op.with_compute_dtype(compute_dtype), self.task
        )

    def fused_cg_step_fn(self, sigma2=None):
        """Not fusable yet: the Kronecker step needs a task contraction
        between the prologue and the tile matmul — a documented frontier.
        Warns (loud, once per operator) and returns None (graceful unfused
        fallback)."""
        _warn_unfused_kronecker(self)
        return None


@_register
@dataclasses.dataclass(frozen=True)
class HadamardKroneckerOperator(LinearOperator):
    """Hadamard multitask covariance for heterogeneous panels.

    Each of the m training rows is one (data point, task) observation with
    its own ``task_ids[i] ∈ [0, T)``:

        K[i, j] = K_X[i, j] · K_T[task_ids[i], task_ids[j]]

    — the Hadamard (elementwise) product of the data kernel with the
    gathered task kernel.  ``matmul`` keeps the one-data-matmul structure
    of the Kronecker case: the RHS is scattered into per-task slots
    (one-hot on the task id), the (m, T·t) block makes ONE
    ``data_op.matmul`` call, and the task kernel rows gathered by task id
    contract the result — O(t·(m²T + mT²)).  On a complete grid (every
    point observed for every task, data-major order) this operator equals
    :class:`KroneckerKernelOperator` entrywise.
    """

    data_op: LinearOperator  # (m, m) over the per-row data coordinates
    task: jax.Array  # (T, T)
    task_ids: jax.Array  # (m,) int32 task of each observation row

    @property
    def shape(self):
        m = self.data_op.shape[0]
        return (m, m)

    @property
    def num_tasks(self) -> int:
        return self.task.shape[0]

    @property
    def dtype(self):
        return self.data_op.dtype

    def matmul(self, M):
        squeeze = M.ndim == 1
        if squeeze:
            M = M[:, None]
        T = self.task.shape[0]
        m = self.data_op.shape[0]
        t = M.shape[-1]
        batch = M.shape[:-2]
        onehot = jax.nn.one_hot(self.task_ids, T, dtype=M.dtype)  # (m, T)
        expanded = (onehot[:, :, None] * M[..., :, None, :]).reshape(
            *batch, m, T * t
        )
        Y = self.data_op.matmul(expanded).reshape(*batch, m, T, t)
        rows = self.task[self.task_ids]  # (m, T) gathered task-kernel rows
        out = jnp.sum(rows[:, :, None] * Y, axis=-2)
        return out[..., 0] if squeeze else out

    def diagonal(self):
        return self.data_op.diagonal() * jnp.diagonal(self.task)[self.task_ids]

    def row(self, i):
        return self.data_op.row(i) * self.task[self.task_ids[i]][self.task_ids]

    def prepare(self):
        return HadamardKroneckerOperator(
            self.data_op.prepare(), self.task, self.task_ids
        )

    def with_compute_dtype(self, compute_dtype):
        return HadamardKroneckerOperator(
            self.data_op.with_compute_dtype(compute_dtype), self.task, self.task_ids
        )

    def fused_cg_step_fn(self, sigma2=None):
        _warn_unfused_kronecker(self)
        return None


@_register
@dataclasses.dataclass(frozen=True)
class KroneckerAddedDiagOperator(LinearOperator):
    """K̂ = K_multitask + Σ_noise with per-task noise σ²_τ.

    The multitask analogue of :class:`AddedDiagOperator`: in the
    data-major Kronecker layout the noise is I_n ⊗ diag(σ²) (row i·T+τ
    gets σ²_τ); for a Hadamard base the per-row noise is the task-id
    gather σ²_{task_ids[i]}.  ``task_ids=None`` selects the tiled
    Kronecker layout.  ``diagonal()`` is exact (base diagonal + per-row
    noise), which is what keeps cached Rayleigh–Ritz variances
    conservative; ``with_compute_dtype`` recurses into the base while the
    noise stays f32.
    """

    base: LinearOperator  # Kronecker or Hadamard multitask kernel
    task_noise: jax.Array  # (T,) per-task σ²ₜ (scalar = shared)
    task_ids: jax.Array | None = None  # (m,) int32, None → tiled grid layout

    @property
    def shape(self):
        return self.base.shape

    @property
    def dtype(self):
        return self.base.dtype

    def _row_noise(self):
        noise = jnp.asarray(self.task_noise)
        m = self.base.shape[0]
        if noise.ndim == 0:
            return jnp.full((m,), noise)
        if self.task_ids is None:
            return jnp.tile(noise, m // noise.shape[0])
        return noise[self.task_ids]

    def matmul(self, M):
        noise = self._row_noise()
        if M.ndim == 1:
            return self.base.matmul(M) + noise * M
        return self.base.matmul(M) + noise[:, None] * M

    def diagonal(self):
        return self.base.diagonal() + self._row_noise()

    def row(self, i):
        return self.base.row(i).at[i].add(self._row_noise()[i])

    def prepare(self):
        return KroneckerAddedDiagOperator(
            self.base.prepare(), self.task_noise, self.task_ids
        )

    def with_compute_dtype(self, compute_dtype):
        # noise stays f32 — only the multitask kernel matmul reduces
        return KroneckerAddedDiagOperator(
            self.base.with_compute_dtype(compute_dtype),
            self.task_noise,
            self.task_ids,
        )

    def fused_cg_step_fn(self, sigma2=None):
        _warn_unfused_kronecker(self)
        return None


@_register
@dataclasses.dataclass(frozen=True)
class BatchDenseOperator(LinearOperator):
    """Stack of b independent dense operators (block-diagonal view) — used
    for multi-task / batched GPs. Shape reported is a single block; matmul
    takes (b, n, t)."""

    matrices: jax.Array  # (b, n, n)
    compute_dtype: str = static_field(default="float32")

    @property
    def shape(self):
        return self.matrices.shape[-2:]

    @property
    def batch(self):
        return self.matrices.shape[0]

    @property
    def dtype(self):
        return self.matrices.dtype

    def matmul(self, M):
        if is_reduced(self.compute_dtype):
            return _mixed_matmul(self.matrices, M)
        return self.matrices @ M  # broadcasts (b,n,n) @ (..., n, t)

    def with_compute_dtype(self, compute_dtype):
        return dataclasses.replace(
            self, compute_dtype=normalize_compute_dtype(compute_dtype)
        )

    def diagonal(self):
        return jax.vmap(jnp.diagonal)(self.matrices)


@_register
@dataclasses.dataclass(frozen=True)
class CallableOperator(LinearOperator):
    """Fully blackbox operator: user supplies the matmul closure plus the
    cheap accessors.  ``params`` is an arbitrary differentiable pytree passed
    to every callback — gradients flow through it."""

    params: Any
    matmul_fn: Callable = static_field()
    row_fn: Callable | None = static_field(default=None)
    diag_fn: Callable | None = static_field(default=None)
    n: int = static_field(default=0)
    _dtype: Any = static_field(default=jnp.float32)

    @property
    def shape(self):
        return (self.n, self.n)

    @property
    def dtype(self):
        return self._dtype

    def matmul(self, M):
        return self.matmul_fn(self.params, M)

    def row(self, i):
        if self.row_fn is None:
            return super().row(i)
        return self.row_fn(self.params, i)

    def diagonal(self):
        if self.diag_fn is None:
            return super().diagonal()
        return self.diag_fn(self.params)


# --- partitioned kernel streaming (million-row exact GPs) -------------------


@dataclasses.dataclass(frozen=True)
class PanelLaunch:
    """Trace-time accounting record for one partitioned ``matmul``.

    This is the assertion surface for the partitioned path's memory
    contract: the peak live kernel slab is ONE (panel_rows × n) tile, never
    the (n × n) matrix.  Tests assert ``panel_rows < n`` on every recorded
    launch; the million benchmark turns ``panel_bytes`` vs ``dense_bytes``
    into its memory table."""

    n: int
    rhs_cols: int
    batch: int
    panel_rows: int
    num_panels: int
    backend: str
    sharded: bool
    devices: int = 1
    itemsize: int = 4
    #: True when this record is a panel-fused CG step (one fused launch per
    #: panel per iteration) rather than a plain streamed matmul — the
    #: accounting surface for "launches per CG iteration == num_panels"
    fused: bool = False

    @property
    def panel_bytes(self) -> int:
        """Peak live working set of one streamed panel: the (p × n) kernel
        slab (materialized outright by the XLA backend; an upper bound for
        the Pallas backend, which holds only (bn × bm) VMEM tiles) plus the
        panel's accumulated output rows."""
        return self.itemsize * self.panel_rows * (
            self.n + self.rhs_cols * max(self.batch, 1)
        )

    @property
    def dense_bytes(self) -> int:
        """What materializing K would cost instead."""
        return 4 * self.n * self.n


_PANEL_SINK = threading.local()


@contextmanager
def panel_accounting(into=None):
    """Collect a :class:`PanelLaunch` per partitioned matmul *traced* in the
    block (mirrors :func:`repro.core.health.collect`).  Recording happens at
    trace time — one record per distinct matmul in the program, including
    matmuls inside a jitted CG scan (traced once, executed per iteration)."""
    launches = [] if into is None else into
    prev = getattr(_PANEL_SINK, "launches", None)
    _PANEL_SINK.launches = launches
    try:
        yield launches
    finally:
        _PANEL_SINK.launches = prev


def _record_panels(launch: PanelLaunch):
    """Deliver one trace-time PanelLaunch to every installed sink.

    Three sinks, same record: the :func:`panel_accounting` list (tests and
    the million benchmark), the obs metrics registry (launch / byte
    counters), and the obs trace (one ``panel_launch`` span per record, so
    a trace's panel-span count equals ``panel_accounting()``'s list length
    by construction).  All are no-ops when nothing is installed."""
    sink = getattr(_PANEL_SINK, "launches", None)
    if sink is not None:
        sink.append(launch)
    if obs.active() is not None:
        labels = dict(
            backend=launch.backend,
            fused=str(launch.fused).lower(),
            sharded=str(launch.sharded).lower(),
        )
        obs.inc("panel_matmuls_traced_total", **labels)
        obs.inc("panel_launches_traced_total", launch.num_panels, **labels)
        obs.inc(
            "panel_bytes_streamed_total",
            launch.panel_bytes * launch.num_panels,
            **labels,
        )
        obs.set_gauge("panel_rows", launch.panel_rows, backend=launch.backend)
    if obs.active_trace() is not None:
        col = obs.active_trace()
        ts = col.now_us()
        col.add_complete(
            "panel_launch",
            ts,
            0.0,  # trace-time record: the span marks the launch, not a wall
            {
                "n": launch.n,
                "panel_rows": launch.panel_rows,
                "num_panels": launch.num_panels,
                "backend": launch.backend,
                "fused": launch.fused,
                "sharded": launch.sharded,
            },
        )


def _pallas_panel_matmul(
    Xs_rows, Xs_cols, M, outputscale, panel_rows, row0, *, kernel_type, compute_dtype
):
    """Stream K(X_rows, X_cols) @ M through the Pallas kernel one
    (panel_rows × n) row-panel at a time.

    Each panel is one ``fused_kernel_matmul_prescaled`` launch on a
    ``dynamic_slice`` of the pre-scaled rows with the panel's global
    ``row_offset`` — the in-kernel edge-masking/row-offset machinery from
    PR 1 doing what it was built for.  ``row0`` may be traced (the sharded
    path passes each device's band start).  Output is f32 (…, rows, t)."""
    from repro.kernels.kernel_matmul.ops import fused_kernel_matmul_prescaled

    n_rows = Xs_rows.shape[0]
    p = int(panel_rows)
    num = -(-n_rows // p)
    pad = num * p - n_rows
    Xp = jnp.pad(Xs_rows, ((0, pad), (0, 0))) if pad else Xs_rows

    def one_panel(start):
        Xpan = jax.lax.dynamic_slice_in_dim(Xp, start, p, axis=0)
        return fused_kernel_matmul_prescaled(
            Xpan,
            Xs_cols,
            M,
            outputscale,
            jnp.float32(0.0),
            row_offset=row0 + start,
            kernel_type=kernel_type,
            compute_dtype=compute_dtype,
        )

    outs = jax.lax.map(one_panel, jnp.arange(num) * p)  # (num, ..., p, t)
    out = jnp.moveaxis(outs, 0, -3)  # (..., num, p, t)
    out = out.reshape(*out.shape[:-3], num * p, out.shape[-1])
    return out[..., :n_rows, :]


def _xla_panel_matmul(kernel, X_rows, X_cols, M, panel_rows, *, compute_dtype):
    """Streamed row-panel matmul with the kernel evaluated as plain XLA ops
    (the differentiable / CPU-fast formulation; mirrors
    ``repro.core.distributed._local_block_matmul``).

    Each panel body is under ``jax.checkpoint``: the backward pass
    rematerializes one (panel_rows × n) kernel slab at a time instead of
    keeping every panel live — MLL gradients at n=10⁵ fit in memory."""
    compute_dtype = normalize_compute_dtype(compute_dtype)
    reduced = is_reduced(compute_dtype)
    n_rows, d = X_rows.shape
    p = int(panel_rows)
    num = -(-n_rows // p)
    pad = num * p - n_rows
    Xp = jnp.pad(X_rows, ((0, pad), (0, 0))) if pad else X_rows

    @jax.checkpoint
    def one_panel(Xpan):
        tile = kernel(Xpan, X_cols)
        if reduced:
            return _mixed_matmul(tile, M.astype(jnp.bfloat16))
        return jnp.matmul(
            tile.astype(jnp.float32),
            M.astype(jnp.float32),
            preferred_element_type=jnp.float32,
        )

    outs = jax.lax.map(one_panel, Xp.reshape(num, p, d))  # (num, ..., p, t)
    out = jnp.moveaxis(outs, 0, -3)
    out = out.reshape(*out.shape[:-3], num * p, out.shape[-1])
    return out[..., :n_rows, :]


def _xla_band_fused_step(
    kernel,
    X_band,
    X_cols,
    U,
    R,
    D,
    V,
    D2_cols,
    alpha,
    beta,
    gamma,
    sigma2,
    panel_rows,
    *,
    compute_dtype,
):
    """One whole CG iteration over a contiguous row band, streamed one
    (panel_rows × n) kernel slab at a time — the XLA-backend twin of
    ``ops._panel_fused_cg_step_bands``.

    Same math as the fused Pallas kernel: the pending rank-1 updates
    (U += α∘D, R −= α∘V) and this iteration's direction D₂ = γ∘R₂ + β∘D
    are elementwise over the band's own rows (touched once per iteration);
    the O(rows·n) work — V₂ = K̂·D₂ — consumes ``D2_cols``, the SAME full
    new direction recomputed from the previous iteration's column-side
    state on every device, one kernel panel per scan step.  The
    ``[dᵀV; rᵀr; rᵀV; vᵀV]`` partials are band-row sums accumulated in a
    loop-carried (…, t) slab per panel, in panel order (a left fold from
    zeros — the order the sharded path's ``ordered_psum`` reproduces).
    Not checkpointed: the fused step is solve-only machinery; MLL
    gradients flow through the matmul custom VJP, never through here."""
    compute_dtype = normalize_compute_dtype(compute_dtype)
    reduced = is_reduced(compute_dtype)
    rows = X_band.shape[0]
    p = max(1, min(int(panel_rows), rows))
    num = rows // p
    rem = rows - num * p
    a = alpha[..., None, :]
    b_ = beta[..., None, :]
    g = gamma[..., None, :]
    U2 = U + a * D
    R2 = R - a * V
    D2 = g * R2 + b_ * D  # the band's rows of D2_cols, computed locally
    s2 = jnp.asarray(sigma2, jnp.float32)
    Mc = (
        D2_cols.astype(jnp.bfloat16)
        if reduced
        else D2_cols.astype(jnp.float32)
    )
    lead = U.shape[:-2]
    t = U.shape[-1]

    def panel_mvm(Xp, D2p):
        tile = kernel(Xp, X_cols)
        if reduced:
            out = _mixed_matmul(tile, Mc)
        else:
            out = jnp.matmul(
                tile.astype(jnp.float32), Mc, preferred_element_type=jnp.float32
            )
        return out + s2 * D2p

    def partials(D2p, R2p, V2p):
        return (
            jnp.sum(D2p * V2p, axis=-2),
            jnp.sum(R2p * R2p, axis=-2),
            jnp.sum(R2p * V2p, axis=-2),
            jnp.sum(V2p * V2p, axis=-2),
        )

    red = tuple(jnp.zeros(lead + (t,), jnp.float32) for _ in range(4))

    def one_panel(red, start):
        Xp = jax.lax.dynamic_slice_in_dim(X_band, start, p, axis=0)
        D2p = jax.lax.dynamic_slice_in_dim(D2, start, p, axis=-2)
        R2p = jax.lax.dynamic_slice_in_dim(R2, start, p, axis=-2)
        V2p = panel_mvm(Xp, D2p)
        red = jax.tree_util.tree_map(jnp.add, red, partials(D2p, R2p, V2p))
        return red, V2p

    red, V2s = jax.lax.scan(one_panel, red, jnp.arange(num) * p)
    V2 = jnp.moveaxis(V2s, 0, -3)
    V2 = V2.reshape(*V2.shape[:-3], num * p, V2.shape[-1])
    if rem:
        # non-dividing tail: one exact-height panel, never padded rows
        # (zero-pad rows would contribute σ²-diagonal terms to vᵀV)
        D2p = D2[..., num * p :, :]
        V2p = panel_mvm(X_band[num * p :], D2p)
        red = jax.tree_util.tree_map(
            jnp.add, red, partials(D2p, R2[..., num * p :, :], V2p)
        )
        V2 = jnp.concatenate([V2, V2p], axis=-2)
    return U2, R2, D2, V2, red


def _xla_panel_fused_step(
    kernel, X, U, R, D, V, alpha, beta, gamma, sigma2, panel_rows, *, compute_dtype
):
    """Single-device XLA-backend panel-fused CG step (band == full range)."""
    a = alpha[..., None, :]
    D2_cols = (
        gamma[..., None, :] * (R - a * V) + beta[..., None, :] * D
    )
    return _xla_band_fused_step(
        kernel, X, X, U, R, D, V, D2_cols, alpha, beta, gamma, sigma2,
        panel_rows, compute_dtype=compute_dtype,
    )


def _sharded_xla_panel_fused_step(
    op, U, R, D, V, alpha, beta, gamma, sigma2, panel_rows, mesh, shards
):
    """shard_map twin of :func:`_xla_panel_fused_step`: each device
    all-gathers the column-side (R, D, V) state, recomputes the full new
    direction, streams its own contiguous row band through
    :func:`_xla_band_fused_step`, and the (4, t) reductions are combined
    across devices ONCE per iteration with the deterministic
    ``ordered_psum`` fold (bitwise-matching a single device scanning the
    same panels when panel_rows divides the band height)."""
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import (
        ordered_psum,
        row_shard_spec,
        unchecked_shard_map,
    )

    axes = op.data_axes
    n = op.shape[0]
    n_loc = n // shards
    row_axis = U.ndim - 2
    kern_leaves, kern_def = jax.tree_util.tree_flatten(op.kernel)
    kern_leaves = tuple(kern_leaves)
    compute_dtype = op.compute_dtype

    def body(leaves, X_full, U_loc, R_loc, D_loc, V_loc, al, be, ga, s2):
        kernel = jax.tree_util.tree_unflatten(kern_def, leaves)
        R_full = jax.lax.all_gather(R_loc, axes, axis=row_axis, tiled=True)
        D_full = jax.lax.all_gather(D_loc, axes, axis=row_axis, tiled=True)
        V_full = jax.lax.all_gather(V_loc, axes, axis=row_axis, tiled=True)
        idx = jax.lax.axis_index(axes)
        X_band = jax.lax.dynamic_slice_in_dim(
            X_full, idx * n_loc, n_loc, axis=0
        )
        a = al[..., None, :]
        D2_cols = ga[..., None, :] * (R_full - a * V_full) + be[..., None, :] * D_full
        U2, R2, D2, V2, red = _xla_band_fused_step(
            kernel, X_band, X_full, U_loc, R_loc, D_loc, V_loc, D2_cols,
            al, be, ga, s2, panel_rows, compute_dtype=compute_dtype,
        )
        red = jax.tree_util.tree_map(lambda x: ordered_psum(x, axes), red)
        return U2, R2, D2, V2, red

    state_spec = row_shard_spec(U.ndim, axes)
    rep = P(*([None] * (U.ndim - 1)))
    x_spec = P(*([None] * op.X.ndim))
    return unchecked_shard_map(
        body,
        mesh,
        in_specs=(
            tuple(P() for _ in kern_leaves),
            x_spec,
            state_spec,
            state_spec,
            state_spec,
            state_spec,
            rep,
            rep,
            rep,
            P(),
        ),
        out_specs=(state_spec, state_spec, state_spec, state_spec, (rep, rep, rep, rep)),
    )(
        kern_leaves,
        op.X,
        U,
        R,
        D,
        V,
        alpha,
        beta,
        gamma,
        jnp.asarray(sigma2, jnp.float32),
    )


def _sharded_panel_matmul(op, M, mesh, shards):
    """Multi-device partitioned matmul: each device owns a contiguous row
    band (panel ranges assigned by ``shard_map``), streams its band's
    panels locally, and the row-sharded results are concatenated.  The one
    collective is the all-gather of M (cast to ``compute_dtype`` first, so
    the mixed policy halves the payload)."""
    from jax.sharding import PartitionSpec as P

    from repro.core.precision import as_jnp_dtype
    from repro.distributed.sharding import (
        row_shard_spec,
        unchecked_shard_map,
    )

    axes = op.data_axes
    n = op.shape[0]
    if n % shards != 0:
        raise ValueError(
            f"partitioned sharding needs n divisible by the device count: "
            f"n={n}, shards={shards}"
        )
    n_loc = n // shards
    p = min(op.panel_rows_for(n), n_loc)
    backend = op.resolved_backend
    row_axis = M.ndim - 2
    Xdat = op.Xs if backend == "pallas" else op.X
    kern_leaves, kern_def = jax.tree_util.tree_flatten(op.kernel)
    kern_leaves = tuple(kern_leaves)
    compute_dtype = op.compute_dtype

    # kernel leaves ride as explicit operands (closure capture of traced
    # values breaks vjp tracing through shard_map; same idiom as
    # repro.core.distributed.ShardedKernelOperator)
    def body(leaves, X_full, M_loc):
        kernel = jax.tree_util.tree_unflatten(kern_def, leaves)
        M_full = jax.lax.all_gather(M_loc, axes, axis=row_axis, tiled=True)
        idx = jax.lax.axis_index(axes)
        start = idx * n_loc
        X_band = jax.lax.dynamic_slice_in_dim(X_full, start, n_loc, axis=0)
        if backend == "pallas":
            return _pallas_panel_matmul(
                X_band,
                X_full,
                M_full,
                kernel.outputscale,
                p,
                start,
                kernel_type=op.kernel_type,
                compute_dtype=compute_dtype,
            )
        return _xla_panel_matmul(
            kernel, X_band, X_full, M_full, p, compute_dtype=compute_dtype
        )

    x_spec = P(*([None] * Xdat.ndim))
    out = unchecked_shard_map(
        body,
        mesh,
        in_specs=(
            tuple(P() for _ in kern_leaves),
            x_spec,
            row_shard_spec(M.ndim, axes),
        ),
        out_specs=row_shard_spec(M.ndim, axes),
    )(
        kern_leaves,
        Xdat,
        M.astype(as_jnp_dtype(compute_dtype)) if backend == "pallas" else M,
    )
    return out


@jax.custom_vjp
def _partitioned_matmul(op, M):
    """K @ M via streamed row-panels, with hand-wired gradients.

    The primal runs the selected backend (Pallas launches or checkpointed
    XLA panels, possibly sharded).  The VJP re-expresses the matmul as the
    *checkpointed XLA panel stream* and differentiates that — so (a) the
    backward pass also streams panels (never all slabs live at once), and
    (b) ``mode="pallas_partitioned"`` trains natively even though
    interpret-mode ``pallas_call`` has no jvp rule on this jax pin (the PR 6
    gap): jax never differentiates through the Pallas launch at all."""
    return op._forward_matmul(M)


def _partitioned_matmul_fwd(op, M):
    return op._forward_matmul(M), (op, M)


def _partitioned_matmul_bwd(res, ct):
    op, M = res
    n = op.shape[0]
    p = min(op.panel_rows_for(n), n)

    def ref(kernel, X, m):
        return _xla_panel_matmul(
            kernel, X, X, m, p, compute_dtype=op.compute_dtype
        )

    _, vjp = jax.vjp(ref, op.kernel, op.X, M)
    kern_bar, X_bar, M_bar = vjp(ct)
    # cotangent for the op pytree: kernel/X get the reference-formulation
    # grads; the pre-scaled Xs cache (a pure function of kernel.lengthscale
    # and X, both already accounted for) gets zeros
    op_bar = dataclasses.replace(
        op,
        kernel=kern_bar,
        X=X_bar,
        Xs=None if op.Xs is None else jnp.zeros_like(op.Xs),
    )
    return op_bar, M_bar


_partitioned_matmul.defvjp(_partitioned_matmul_fwd, _partitioned_matmul_bwd)


@jax.custom_vjp
def _sharded_partitioned_matmul(op, M):
    """Sharded K @ M with a *band-sharded* backward pass.

    The primal is :func:`_sharded_panel_matmul` (each device streams its
    contiguous row band).  The VJP re-expresses each device's band as the
    checkpointed XLA panel stream — ``K[band, :] @ M`` — and differentiates
    that band ON ITS OWN DEVICE at the band's rows of the cotangent, then
    ``psum``s the (kernel, X, M) contributions; the gradient pass
    re-streams panels on all devices instead of serializing through one.
    X appears as both the band rows (sliced inside the vjp'd function) and
    the full column set, so one ``jax.vjp`` accounts for both paths of
    dK/dX.  ``op.mesh`` must carry the resolved mesh (the caller pins it
    with ``dataclasses.replace`` — it is a static field, so it rides in
    the pytree aux data through jit/grad)."""
    mesh = op.mesh
    return _sharded_panel_matmul(op, M, mesh, op._num_shards(mesh))


def _sharded_partitioned_matmul_fwd(op, M):
    mesh = op.mesh
    return _sharded_panel_matmul(op, M, mesh, op._num_shards(mesh)), (op, M)


def _sharded_partitioned_matmul_bwd(res, ct):
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import row_shard_spec, unchecked_shard_map

    op, M = res
    mesh = op.mesh
    shards = op._num_shards(mesh)
    axes = op.data_axes
    n = op.shape[0]
    n_loc = n // shards
    p = min(op.panel_rows_for(n), n_loc)
    row_axis = M.ndim - 2
    kern_leaves, kern_def = jax.tree_util.tree_flatten(op.kernel)
    kern_leaves = tuple(kern_leaves)
    compute_dtype = op.compute_dtype

    def body(leaves, X_full, M_loc, ct_loc):
        kernel = jax.tree_util.tree_unflatten(kern_def, leaves)
        M_full = jax.lax.all_gather(M_loc, axes, axis=row_axis, tiled=True)
        idx = jax.lax.axis_index(axes)

        def ref(kernel, X, m):
            X_band = jax.lax.dynamic_slice_in_dim(
                X, idx * n_loc, n_loc, axis=0
            )
            return _xla_panel_matmul(
                kernel, X_band, X, m, p, compute_dtype=compute_dtype
            )

        _, vjp = jax.vjp(ref, kernel, X_full, M_full)
        kern_bar, X_bar, M_bar = vjp(ct_loc)
        # each device differentiated its own output band; the total
        # gradient is the sum of the per-band contributions
        kb_leaves = tuple(jax.tree_util.tree_leaves(kern_bar))
        kb_leaves = jax.lax.psum(kb_leaves, axes)
        return kb_leaves, jax.lax.psum(X_bar, axes), jax.lax.psum(M_bar, axes)

    x_spec = P(*([None] * op.X.ndim))
    ct_spec = row_shard_spec(M.ndim, axes)
    rep_m = P(*([None] * M.ndim))
    kb_leaves, X_bar, M_bar = unchecked_shard_map(
        body,
        mesh,
        in_specs=(
            tuple(P() for _ in kern_leaves),
            x_spec,
            ct_spec,
            ct_spec,
        ),
        out_specs=(tuple(P() for _ in kern_leaves), x_spec, rep_m),
    )(kern_leaves, op.X, M, ct)
    kern_bar = jax.tree_util.tree_unflatten(kern_def, list(kb_leaves))
    op_bar = dataclasses.replace(
        op,
        kernel=kern_bar,
        X=X_bar,
        Xs=None if op.Xs is None else jnp.zeros_like(op.Xs),
    )
    return op_bar, M_bar


_sharded_partitioned_matmul.defvjp(
    _sharded_partitioned_matmul_fwd, _sharded_partitioned_matmul_bwd
)


@_register
@dataclasses.dataclass(frozen=True)
class PartitionedKernelOperator(LinearOperator):
    """K(X, X) streamed one (panel_rows × n) row-panel at a time — the
    operator that makes "n is bounded by O(n²) memory" false.

    No mode of this operator ever materializes K: ``matmul`` computes each
    panel from (X_panel, X) on the fly (Wang et al. 2019, "Exact Gaussian
    Processes on a Million Data Points") and accumulates into the (n, t)
    output, so peak memory is O(n·(d + t)) persistent state plus one
    (panel_rows × n) transient slab bounded by ``panel_budget_bytes``.

    Backends (``backend=``):

      * ``"pallas"`` — one ``fused_kernel_matmul_prescaled`` launch per
        panel on pre-scaled inputs via the ``row_offset`` path; composes
        with the native batch grid and the bf16 tile policy (f32
        accumulation).
      * ``"xla"``    — the kernel evaluated as plain XLA ops per panel
        under ``jax.checkpoint`` (differentiable; also the faster choice
        under interpret-mode Pallas on CPU).
      * ``"auto"``   — pallas on TPU, xla elsewhere.

    Gradients always flow through the checkpointed XLA panel stream via
    ``_partitioned_matmul``'s custom VJP, so training never holds all
    panels live and never differentiates a ``pallas_call``.

    Multi-device: when ``data_axes`` names axes of an available mesh
    (explicit ``mesh=`` or the ambient ``jax.sharding`` context), each
    device owns a contiguous row band and streams its panels locally
    (results concatenated by ``shard_map``).  ``row()``/``diagonal()`` are
    exact O(n)/O(n·d) primitives feeding the pivoted-Cholesky
    preconditioner without touching the panel loop.
    """

    kernel: Any  # stationary kernel pytree (RBF/Matérn — needs __call__/diag)
    X: jax.Array  # (n, d) raw inputs
    Xs: jax.Array | None = None  # prepare()-cached pre-scaled inputs
    kernel_type: str = static_field(default="rbf")
    panel_rows: int = static_field(default=0)  # 0 → budget auto-chooser
    panel_budget_bytes: int = static_field(default=0)  # 0 → ops default
    backend: str = static_field(default="auto")  # auto | pallas | xla
    data_axes: tuple = static_field(default=("data",))
    mesh: Any = static_field(default=None)
    compute_dtype: str = static_field(default="float32")

    def __post_init__(self):
        if self.backend not in ("auto", "pallas", "xla"):
            raise ValueError(
                f"backend must be 'auto', 'pallas' or 'xla', got {self.backend!r}"
            )

    # -- shape / dtype -----------------------------------------------------
    @property
    def shape(self):
        n = self.X.shape[0]
        return (n, n)

    @property
    def dtype(self):
        return jnp.float32  # panel accumulation is always f32

    # -- panel geometry ----------------------------------------------------
    @property
    def resolved_backend(self) -> str:
        if self.backend != "auto":
            return self.backend
        from repro.kernels.kernel_matmul.ops import _on_tpu

        return "pallas" if _on_tpu() else "xla"

    def panel_rows_for(self, n) -> int:
        """Effective panel height: the explicit knob, else the
        VMEM/HBM-budget auto-chooser."""
        from repro.kernels.kernel_matmul.ops import choose_panel_rows

        if self.panel_rows > 0:
            return max(1, min(self.panel_rows, n))
        return choose_panel_rows(
            n, budget_bytes=self.panel_budget_bytes or None
        )

    def _live_mesh(self):
        """The mesh this matmul shards over, or None for single-device."""
        if self.mesh is not None:
            return self.mesh
        if not self.data_axes:
            return None
        from repro.distributed.sharding import current_mesh, mesh_axis_sizes

        mesh = current_mesh()
        if mesh is None:
            return None
        sizes = mesh_axis_sizes(mesh)
        if any(a not in sizes for a in self.data_axes):
            return None
        return mesh

    def _num_shards(self, mesh) -> int:
        if mesh is None:
            return 1
        from repro.distributed.sharding import mesh_axis_sizes

        sizes = mesh_axis_sizes(mesh)
        shards = 1
        for a in self.data_axes:
            shards *= sizes[a]
        return shards

    # -- matmul ------------------------------------------------------------
    def matmul(self, M):
        squeeze = M.ndim == 1
        if squeeze:
            M = M[:, None]
        op = self._ready()
        n = op.shape[0]
        mesh = op._live_mesh()
        shards = op._num_shards(mesh)
        if shards > 1 and n % shards != 0:
            # fall back loudly to single-device rather than mis-sharding
            warnings.warn(
                f"partitioned matmul: n={n} not divisible by {shards} "
                f"devices; running single-device",
                stacklevel=2,
            )
            mesh, shards = None, 1
        p = op.panel_rows_for(n)
        n_band = n // shards
        p_eff = min(p, n_band)
        num_panels = shards * (-(-n_band // p_eff))
        from repro.core.precision import as_jnp_dtype

        _record_panels(
            PanelLaunch(
                n=n,
                rhs_cols=M.shape[-1],
                batch=int(np.prod(M.shape[:-2], dtype=np.int64)) if M.ndim > 2 else 1,
                panel_rows=p_eff,
                num_panels=num_panels,
                backend=op.resolved_backend,
                sharded=shards > 1,
                devices=shards,
                itemsize=jnp.dtype(as_jnp_dtype(op.compute_dtype)).itemsize,
            )
        )
        if shards > 1:
            # pin the resolved mesh into the (static) mesh field so the
            # custom-VJP backward can rebuild the same shard_map — the
            # gradient pass then re-streams panels on all devices too
            out = _sharded_partitioned_matmul(
                dataclasses.replace(op, mesh=mesh), M
            )
        else:
            out = _partitioned_matmul(op, M)
        return out[..., 0] if squeeze else out

    def _ready(self) -> "PartitionedKernelOperator":
        if self.resolved_backend == "pallas" and self.Xs is None:
            return self.prepare()
        return self

    def _forward_matmul(self, M):
        """Single-device primal for the custom-VJP seam."""
        n = self.shape[0]
        p = min(self.panel_rows_for(n), n)
        if self.resolved_backend == "pallas":
            return _pallas_panel_matmul(
                self.Xs,
                self.Xs,
                M,
                self.kernel.outputscale,
                p,
                0,
                kernel_type=self.kernel_type,
                compute_dtype=self.compute_dtype,
            )
        return _xla_panel_matmul(
            self.kernel, self.X, self.X, M, p, compute_dtype=self.compute_dtype
        )

    # -- exact cheap accessors (feed the pivoted-Cholesky preconditioner) --
    def diagonal(self):
        return self.kernel.diag(self.X).astype(jnp.float32)

    def row(self, i):
        return self.kernel(self.X[i][None, :], self.X)[0].astype(jnp.float32)

    # -- solver preparation / precision ------------------------------------
    def prepare(self):
        if self.Xs is not None or self.resolved_backend != "pallas":
            return self
        from repro.kernels.kernel_matmul.ops import (
            _stationary_kernel_type,
            prescale_inputs,
        )

        return dataclasses.replace(
            self,
            Xs=prescale_inputs(self.X, self.kernel.lengthscale, self.compute_dtype),
            kernel_type=_stationary_kernel_type(self.kernel),
        )

    def with_compute_dtype(self, compute_dtype):
        compute_dtype = normalize_compute_dtype(compute_dtype)
        if compute_dtype == self.compute_dtype:
            return self
        # drop the prescale cache: it is stored at the old dtype
        return dataclasses.replace(self, compute_dtype=compute_dtype, Xs=None)

    def fused_cg_step_fn(self, sigma2=None):
        """Panel-fused CG step: the PR 4 fused iteration launched once per
        (panel_rows × n) row-panel via the ``row_offset`` path, with the
        partial ``[dᵀV; rᵀr; rᵀV; vᵀV]`` reductions carried across the
        panel loop — one launch per panel per CG iteration instead of the
        unfused loop's per-panel matmul plus ~10 XLA state passes, and
        never an (n × n) working set.

        Sharded, each device streams its contiguous row band through the
        fused step and the (4, t) reductions are combined across devices
        once per iteration in deterministic device order, so 1-device and
        N-device fused solves stay bitwise-equal when panel_rows divides
        the band height.  Panel height is chosen at trace time from the
        RHS shape with the *fused* working-set budget
        (``choose_panel_rows(..., fused=True)``)."""
        s2 = jnp.float32(0.0) if sigma2 is None else jnp.asarray(sigma2)
        if s2.ndim:
            _warn_once_per_op(
                self,
                "partitioned_batched_sigma2",
                "fuse_cg=True on the partitioned path with batched noise: "
                "the fused kernel folds one scalar σ² into its diagonal "
                "tile — running the unfused streamed loop.",
            )
            return None
        op = self._ready()
        n = op.shape[0]
        mesh = op._live_mesh()
        shards = op._num_shards(mesh)
        if shards > 1 and n % shards != 0:
            _warn_once_per_op(
                self,
                "partitioned_fused_indivisible",
                f"panel-fused CG: n={n} not divisible by {shards} devices; "
                f"running the fused step single-device",
                )
            mesh, shards = None, 1
        backend = op.resolved_backend
        from repro.core.precision import as_jnp_dtype
        from repro.kernels.kernel_matmul.ops import (
            choose_panel_rows,
            lane_aligned,
            panel_fused_cg_step_prescaled,
            sharded_fused_cg_step_prescaled,
        )

        itemsize = jnp.dtype(as_jnp_dtype(op.compute_dtype)).itemsize
        Xs = None if op.Xs is None else lane_aligned(op.Xs)
        n_band = n // shards

        def step(U, R, D, V, alpha, beta, gamma):
            # shapes are static at trace time: budget the FUSED working set
            # (state-column slabs + carried reductions) for this RHS
            t = U.shape[-1]
            b = int(np.prod(U.shape[:-2], dtype=np.int64)) if U.ndim > 2 else 1
            if op.panel_rows > 0:
                p = max(1, min(op.panel_rows, n_band))
            else:
                p = min(
                    choose_panel_rows(
                        n,
                        budget_bytes=op.panel_budget_bytes or None,
                        itemsize=itemsize,
                        rhs_cols=t,
                        batch=b,
                        fused=True,
                    ),
                    n_band,
                )
            num_band = n_band // p + (1 if n_band % p else 0)
            _record_panels(
                PanelLaunch(
                    n=n,
                    rhs_cols=t,
                    batch=b,
                    panel_rows=p,
                    num_panels=shards * num_band,
                    backend=backend,
                    sharded=shards > 1,
                    devices=shards,
                    itemsize=itemsize,
                    fused=True,
                )
            )
            if backend == "pallas":
                kw = dict(
                    panel_rows=p,
                    kernel_type=op.kernel_type,
                    compute_dtype=op.compute_dtype,
                )
                if shards > 1:
                    return sharded_fused_cg_step_prescaled(
                        Xs, U, R, D, V, alpha, beta, gamma,
                        op.kernel.outputscale, s2, mesh, op.data_axes, **kw,
                    )
                return panel_fused_cg_step_prescaled(
                    Xs, U, R, D, V, alpha, beta, gamma,
                    op.kernel.outputscale, s2, **kw,
                )
            if shards > 1:
                return _sharded_xla_panel_fused_step(
                    op, U, R, D, V, alpha, beta, gamma, s2, p, mesh, shards
                )
            return _xla_panel_fused_step(
                op.kernel, op.X, U, R, D, V, alpha, beta, gamma, s2, p,
                compute_dtype=op.compute_dtype,
            )

        return step


# --- fault injection (robustness harness) ----------------------------------


class FaultSchedule:
    """Seeded, deterministic host-side fault plan for
    :class:`FaultInjectingOperator`.

    One schedule is shared by every prepared / dtype-switched copy of its
    operator (it rides in a static pytree field), so the call counter tracks
    ACTUAL matmul executions — including the ones inside a ``lax.scan`` CG
    loop, where the traced-once matmul still executes once per iteration
    and its ``pure_callback`` ticks the counter each time.

    Attributes are plain and mutable on purpose: a chaos driver toggles
    ``nan_rate`` / ``total_outage`` mid-run against already-jitted solves
    (the callback reads the live object, not a trace-time snapshot).

      * ``nan_calls`` / ``inf_calls`` — exact call indices to corrupt
        (deterministic single-fault experiments);
      * ``nan_rate`` — per-call corruption probability from the seeded rng
        (deterministic given the seed and call order);
      * ``latency_s`` — host sleep per matmul call (operational latency);
      * ``total_outage`` — corrupt EVERY call, including ``to_dense`` (takes
        out the terminal dense ladder rung too: the unhealable fault that
        must trip the serving circuit breaker);
      * ``reduced_only`` — corrupt only reduced-precision (bf16) matmul
        instances, leaving f32 clean — makes the ``precision_f32`` ladder
        rung deterministically heal.

    ``injected`` records ``(call_index, code)`` for every corruption
    actually delivered — the assertion surface for tests.
    """

    NAN = 1.0
    INF = 2.0

    def __init__(
        self,
        seed: int = 0,
        *,
        nan_calls: Sequence[int] = (),
        inf_calls: Sequence[int] = (),
        nan_rate: float = 0.0,
        latency_s: float = 0.0,
        total_outage: bool = False,
        reduced_only: bool = False,
        panel: tuple | None = None,
    ):
        import random

        self._rng = random.Random(seed)
        self._lock = threading.Lock()
        self.nan_calls = frozenset(nan_calls)
        self.inf_calls = frozenset(inf_calls)
        self.nan_rate = float(nan_rate)
        self.latency_s = float(latency_s)
        self.total_outage = bool(total_outage)
        self.reduced_only = bool(reduced_only)
        #: (row_start, num_rows) — corrupt this row band instead of row 0,
        #: targeting a SINGLE panel of a partitioned solve (chaos coverage
        #: for the streamed path: one poisoned panel must not poison the
        #: other panels' rows)
        self.panel = None if panel is None else (int(panel[0]), int(panel[1]))
        self.calls = 0
        self.injected: list = []

    def next_code(self, reduced: bool) -> float:
        """Tick the call counter and decide this call's fate (host side)."""
        import time

        with self._lock:
            idx = self.calls
            self.calls += 1
            if self.latency_s:
                time.sleep(self.latency_s)
            code = 0.0
            if self.total_outage:
                code = self.NAN
            elif self.reduced_only and not reduced:
                code = 0.0
            elif idx in self.nan_calls:
                code = self.NAN
            elif idx in self.inf_calls:
                code = self.INF
            elif self.nan_rate and self._rng.random() < self.nan_rate:
                code = self.NAN
            if code:
                self.injected.append((idx, code))
            return code


@_register
@dataclasses.dataclass(frozen=True)
class FaultInjectingOperator(LinearOperator):
    """Wrap any operator with seeded, deterministic fault injection.

    Three fault families, matching what long-running mixed-precision CG
    actually meets in production:

      * **non-finite matmul outputs** — the schedule corrupts row 0 of the
        matmul result with NaN/Inf on chosen (or seeded-random) calls, via a
        ``jax.pure_callback`` so the decision is made per EXECUTION even
        inside a jitted ``lax.scan`` CG loop;
      * **non-PSD perturbation** — ``negative_diag`` subtracts c·I in-band,
        shifting eigenvalues down (a pathological-hyperparameter stand-in);
      * **latency / outage** — host sleeps and the total-outage mode that
        corrupts everything including ``to_dense``.

    ``diagonal`` / ``row`` delegate CLEAN (so pivoted-Cholesky
    preconditioner construction is not the thing under test).  The wrapper
    forwards the base's fused CG step with the same injection seam wrapped
    around it: a corrupted call poisons the scheduled row band of the
    iteration's V update AND the carried (4, t) reductions — exactly what
    a faulted panel launch would feed the panel-carry accumulator — so
    chaos coverage extends to the panel-fused path (``negative_diag``
    stays unfused-only: it perturbs the operator itself, not one call).

    Wrap INSIDE the noise wrapper — ``AddedDiagOperator(FaultInjecting…(K),
    σ²)`` — so ``build_preconditioner``'s structural dispatch still sees the
    ``AddedDiagOperator`` it requires.
    """

    base: LinearOperator
    schedule: FaultSchedule = static_field(default_factory=FaultSchedule)
    negative_diag: float = static_field(default=0.0)
    reduced: bool = static_field(default=False)

    @property
    def shape(self):
        return self.base.shape

    @property
    def dtype(self):
        return self.base.dtype

    def matmul(self, M):
        out = self.base.matmul(M)
        if self.negative_diag:
            out = out - jnp.asarray(self.negative_diag, out.dtype) * M
        sched = self.schedule
        if sched is None:
            return out
        reduced = self.reduced

        def _decide(_probe):
            return np.float32(sched.next_code(reduced))

        # the probe argument creates a data dependence on THIS iteration's
        # output, so XLA cannot hoist/CSE the (pure) callback out of the CG
        # scan — the schedule must tick once per actual matmul execution
        probe = jnp.real(out.ravel()[0]).astype(jnp.float32)
        code = jax.pure_callback(
            _decide, jax.ShapeDtypeStruct((), jnp.float32), probe
        )
        bad = jnp.where(
            code == FaultSchedule.NAN,
            jnp.nan,
            jnp.where(code == FaultSchedule.INF, jnp.inf, 0.0),
        ).astype(out.dtype)
        span = getattr(sched, "panel", None)
        if out.ndim == 1:
            if span is not None:
                s0, rows = span
                return out.at[s0 : s0 + rows].add(bad)
            return out.at[0].add(bad)
        if span is not None:
            s0, rows = span
            return out.at[..., s0 : s0 + rows, :].add(bad)
        return out.at[..., 0, :].add(bad)

    def diagonal(self):
        d = self.base.diagonal()
        if self.negative_diag:
            d = d - jnp.asarray(self.negative_diag, d.dtype)
        return d

    def row(self, i):
        r = self.base.row(i)
        if self.negative_diag:
            r = r.at[i].add(-jnp.asarray(self.negative_diag, r.dtype))
        return r

    def to_dense(self):
        dense = self.base.to_dense()
        if self.negative_diag:
            n = dense.shape[-1]
            dense = dense - self.negative_diag * jnp.eye(n, dtype=dense.dtype)
        if self.schedule is not None and self.schedule.total_outage:
            # the outage takes the dense fallback path down too — this is
            # the unhealable fault class (→ serving circuit breaker)
            dense = jnp.full_like(dense, jnp.nan)
        return dense

    def fused_cg_step_fn(self, sigma2=None):
        if self.negative_diag:
            # a structural perturbation of K̂ itself — keep it on the
            # unfused loop, whose matmul seam already applies it
            return None
        base_fn = self.base.fused_cg_step_fn(sigma2=sigma2)
        if base_fn is None:
            return None
        sched = self.schedule
        if sched is None:
            return base_fn
        reduced = self.reduced

        def step(U, R, D, V, alpha, beta, gamma):
            Un, Rn, Dn, Vn, red = base_fn(U, R, D, V, alpha, beta, gamma)

            def _decide(_probe):
                return np.float32(sched.next_code(reduced))

            # same per-EXECUTION tick as the matmul seam: the probe's data
            # dependence on this iteration's V keeps the callback inside
            # the CG scan body
            probe = jnp.real(Vn.ravel()[0]).astype(jnp.float32)
            code = jax.pure_callback(
                _decide, jax.ShapeDtypeStruct((), jnp.float32), probe
            )
            bad = jnp.where(
                code == FaultSchedule.NAN,
                jnp.nan,
                jnp.where(code == FaultSchedule.INF, jnp.inf, 0.0),
            ).astype(Vn.dtype)
            span = getattr(sched, "panel", None)
            s0, rows = span if span is not None else (0, 1)
            # the faulted panel's V rows go bad, and so do its epilogue
            # partials — which the panel carry has already summed into the
            # iteration's (4, t) reductions
            Vn = Vn.at[..., s0 : s0 + rows, :].add(bad)
            red = tuple(r + bad.astype(r.dtype) for r in red)
            return Un, Rn, Dn, Vn, red

        return step

    def prepare(self):
        return dataclasses.replace(self, base=self.base.prepare())

    def with_compute_dtype(self, compute_dtype):
        return dataclasses.replace(
            self,
            base=self.base.with_compute_dtype(compute_dtype),
            reduced=self.reduced or is_reduced(compute_dtype),
        )
