"""The `GPModel` protocol — one model-agnostic seam over the BBMM engine.

The paper's promise is that ONE blackbox-matmul routine yields every
inference quantity; this module makes the *model layer* keep that promise.
Every GP variant in ``repro.gp`` (ExactGP, SGPR, SKI, DKL, BLR) implements
the same structural protocol:

    prepare_inputs(X)                     -> data   (hyperparameter-free geometry)
    init_params(X, key=None)              -> params
    operator(params, data)                -> LinearOperator  (the blackbox K̂)
    loss(params, data, y, key)            -> scalar  (-MLL through the engine)
    fit(X, y, *, steps, lr, key, ...)     -> (params, history)   [shared driver]
    posterior_cache(params, data, y)      -> cache   (CG-free serving state)
    predict_cached(params, data, cache, Xstar) -> (mean, var)
    predict(params, data, y, Xstar)       -> (mean, var)

``data`` is whatever ``prepare_inputs`` returned — the raw X for most
models, the grid/interpolation geometry for SKI — so callers (the shared
training driver in ``repro.gp.training``, the serving layer in
``repro.serving``) never special-case a model again.

Streaming models additionally implement the :class:`SupportsStreaming`
extension:

    update_cache(params, data, y, cache, X_new, y_new) -> cache

with ``data``/``y`` already covering the appended block — the seam
``PosteriorSession.observe`` drives.  Two shared implementations live
here:

  * :class:`KrylovCachePredictor` — the exact-GP serving cache
    (``repro.core.PosteriorCache``): Rayleigh–Ritz variances from an
    orthonormal Krylov basis, streaming updates via warm-started CG +
    basis recycling (``extend_posterior_cache``).  ExactGP uses it on raw
    inputs; DKL reduces to it on featurized inputs — the deep-kernel
    feature map lives inside the kernel, so the cache algebra is
    identical; MultitaskGP inherits its cache/update over the (n·T, n·T)
    Kronecker system and overrides only the cross-covariance-dependent
    prediction methods.
  * :class:`WoodburyCachePredictor` — the closed-form low-rank cache for
    models whose kernel IS a low-rank root (SGPR, BLR): all serving state
    lives in the m-dimensional root coordinates (G = RᵀR, b = Rᵀy), so a
    data append is an exact rank-k refresh of two m-sized sufficient
    statistics — O(m³) total, ZERO CG solves, no n-dependence at all.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from repro.core import (
    BBMMSettings,
    build_posterior_cache,
    cached_inv_quad,
    extend_posterior_cache,
    solve as bbmm_solve,
)
from repro.core.precision import f32_matmuls, precision_compute_dtype

#: The structural surface every GP model exposes (checked, without
#: isinstance, by tests/test_serving.py::TestProtocolConformance).
PROTOCOL_METHODS = (
    "prepare_inputs",
    "init_params",
    "operator",
    "loss",
    "fit",
    "posterior_cache",
    "predict_cached",
    "predict",
)

#: The optional streaming extension consumed by PosteriorSession.observe.
STREAMING_METHODS = ("update_cache",)


@runtime_checkable
class GPModel(Protocol):
    """Structural protocol — see the module docstring for the contract."""

    settings: BBMMSettings

    def prepare_inputs(self, X): ...

    def init_params(self, X, key=None): ...

    def operator(self, params, data): ...

    def loss(self, params, data, y, key): ...

    def fit(self, X, y, **kwargs): ...

    def posterior_cache(self, params, data, y): ...

    def predict_cached(self, params, data, cache, Xstar): ...

    def predict(self, params, data, y, Xstar): ...


@runtime_checkable
class SupportsStreaming(Protocol):
    """Models whose serving cache accepts incremental data appends."""

    def update_cache(self, params, data, y, cache, X_new, y_new): ...


def missing_protocol_methods(model, methods=PROTOCOL_METHODS) -> list[str]:
    """Names from ``methods`` the model fails to expose as callables —
    the isinstance-free structural conformance check."""
    return [m for m in methods if not callable(getattr(model, m, None))]


def supports_streaming(model) -> bool:
    return not missing_protocol_methods(model, STREAMING_METHODS)


# ---------------------------------------------------------------------------
# Shared serving-cache implementations
# ---------------------------------------------------------------------------


class KrylovCachePredictor:
    """Exact-GP-style posterior cache + prediction on top of the engine.

    Mixin contract: the model provides ``operator(params, data)``,
    ``kernel(params)`` (whose ``__call__(A, B)``/``diag(A)`` already
    absorb any feature map — DKL's deep kernel featurizes internally),
    ``noise(params)`` and ``settings``.  ``data`` doubles as the training
    inputs fed to the kernel cross-covariance.
    """

    def posterior_cache(self, params, data, y, *, key=None, variance_cache=True):
        """One engine call → reusable solve cache for cheap repeated queries.

        The default key is fixed, so rebuilding the cache for the same
        (params, data, y) is deterministic — and ``predict`` routes its
        mean through this exact code path, making cached and uncached
        means bitwise identical."""
        key = jax.random.PRNGKey(0) if key is None else key
        return build_posterior_cache(
            self.operator(params, data), y, key, self.settings,
            variance_cache=variance_cache,
        )

    def _cross(self, params, data, Xstar):
        """The test-vs-train cross block as a :class:`CrossKernelOperator`
        carrying the model's precision policy — its ``contract`` runs the
        serving-side mean matmul at the same compute dtype as training
        (bitwise-identical plain matmul under "highest")."""
        from .kernels import CrossKernelOperator

        return CrossKernelOperator(
            self.kernel(params), data, Xstar,
            compute_dtype=precision_compute_dtype(self.settings.precision),
        )

    @f32_matmuls
    def predict_cached(self, params, data, cache, Xstar, *, full_cov=False):
        """Serve mean + variance from a PosteriorCache — zero CG iterations.

        Mean: k*ᵀα, O(n·s), contracted under the model's precision policy.
        Variance: Rayleigh–Ritz k*ᵀK̂⁻¹k* from the cached Krylov basis,
        O(n·m) — conservative (never below the exact posterior variance)."""
        kern = self.kernel(params)
        cross = self._cross(params, data, Xstar)
        Kxs = cross.to_dense()  # (n, s) — ONE kernel evaluation per query
        mean = cross.contract(Kxs.T, cache.alpha)
        if full_cov:
            if cache.basis is None:
                raise ValueError(
                    "cache was built with variance_cache=False; rebuild with "
                    "variance_cache=True for covariance queries"
                )
            v = cache.basis.T @ Kxs
            w = jax.scipy.linalg.cho_solve((cache.gram_chol, True), v)
            return mean, kern(Xstar, Xstar) - v.T @ w
        var = kern.diag(Xstar) - cached_inv_quad(cache, Kxs)
        return mean, jnp.clip(var, 1e-8) + self.noise(params)

    @f32_matmuls
    def predict(self, params, data, y, Xstar, *, full_cov=False, key=None):
        """Posterior mean and (diagonal) variance at Xstar (Eq. 1).

        Builds the posterior cache without its variance stage (mean comes
        from the identical mBCG program as ``predict_cached``'s cache, so
        the means are bitwise equal), then runs exact mBCG solves against
        K_X* for the covariance."""
        cache = self.posterior_cache(params, data, y, key=key, variance_cache=False)
        op = self.operator(params, data)
        kern = self.kernel(params)
        cross = self._cross(params, data, Xstar)
        Kxs = cross.to_dense()  # (n, s)
        mean = cross.contract(Kxs.T, cache.alpha)
        # variance: exact solves, reusing the cache's preconditioner factors
        solves = bbmm_solve(op, Kxs, self.settings, precond=cache.precond)
        if full_cov:
            cov = kern(Xstar, Xstar) - Kxs.T @ solves
            return mean, cov
        # predictive (observation) variance: latent var + likelihood noise
        var = kern.diag(Xstar) - jnp.sum(Kxs * solves, axis=0)
        return mean, jnp.clip(var, 1e-8) + self.noise(params)

    def update_cache(self, params, data, y, cache, X_new, y_new):
        """Streaming append: warm-started CG + Krylov-basis recycling.

        ``data``/``y`` are the FULL updated inputs (appended block
        included); the old ``alpha`` seeds the solve and the old basis is
        recycled into the new variance cache — see
        :func:`repro.core.extend_posterior_cache`."""
        return extend_posterior_cache(
            self.operator(params, data), y, cache, self.settings
        )


class WoodburyCache(NamedTuple):
    """Closed-form serving cache for low-rank-root kernels (K̂ = RRᵀ + σ²I).

    Everything queries need lives in the m-dimensional root coordinates:

      G = RᵀR,  b = Rᵀy                      (sufficient statistics)
      chol = chol(σ²I_m + G)
      w = RᵀK̂⁻¹y = (b − G·chol⁻¹b)/σ²        (mean weights)
      H = RᵀK̂⁻¹R = (G − G·chol⁻¹G)/σ²        (variance correction)
      Luu: maps k(X*, U) → root coordinates  (None when the root is direct,
                                              e.g. BLR's scaled features)

    Because (G, b) are *additive* in the data rows, a streaming append is
    an exact rank-k Woodbury refresh: G += RₖᵀRₖ, b += Rₖᵀyₖ, re-derive —
    O(m³), zero CG, no n-dependence (:func:`woodbury_update`).
    """

    G: jax.Array  # (m, m)
    b: jax.Array  # (m,)
    chol: jax.Array  # (m, m)
    w: jax.Array  # (m,)
    H: jax.Array  # (m, m)
    Luu: jax.Array | None  # (m, m) or None
    noise: jax.Array  # scalar σ²


@jax.jit
def _derive_woodbury(G, b, noise, Luu) -> WoodburyCache:
    m = G.shape[0]
    C = jnp.linalg.cholesky(noise * jnp.eye(m, dtype=G.dtype) + G)
    w = (b - G @ jax.scipy.linalg.cho_solve((C, True), b)) / noise
    H = (G - G @ jax.scipy.linalg.cho_solve((C, True), G)) / noise
    return WoodburyCache(G=G, b=b, chol=C, w=w, H=H, Luu=Luu, noise=noise)


def build_woodbury_cache(R, y, noise, Luu=None) -> WoodburyCache:
    """Exact O(n·m²) Woodbury serving cache from the root R (n, m)."""
    return _derive_woodbury(R.T @ R, R.T @ y, noise, Luu)


@jax.jit
def woodbury_update(cache: WoodburyCache, R_new, y_new) -> WoodburyCache:
    """Exact rank-k refresh for k appended rows — O(m³), zero CG, no n.

    jitted with constant m-space shapes, so steady-state serving appends
    compile once and then run at closed-form latency."""
    return _derive_woodbury(
        cache.G + R_new.T @ R_new,
        cache.b + R_new.T @ y_new,
        cache.noise,
        cache.Luu,
    )


@jax.jit
def woodbury_predict(cache: WoodburyCache, Rstar):
    """Mean/variance from the cache for test roots Rstar (s, m) — O(s·m²),
    no solves."""
    mean = Rstar @ cache.w
    var = jnp.sum(Rstar * Rstar, axis=1) - jnp.sum(
        Rstar * (Rstar @ cache.H), axis=1
    )
    return mean, jnp.clip(var, 1e-8) + cache.noise


class WoodburyCachePredictor:
    """Serving cache + prediction for low-rank-root models (SGPR, BLR).

    Mixin contract: the model provides ``noise(params)`` plus two root
    hooks —

      * ``_woodbury_root(params, data) -> (R, Luu)`` — the full training
        root (n, m) and the triangular map into root coordinates (None
        when roots are computed directly from inputs);
      * ``_woodbury_root_rows(params, Luu, Xq) -> (q, m)`` — root rows for
        arbitrary query/append points.

    The posterior algebra is exact for these kernels, so ``predict``
    *routes through the cache* (no CG anywhere) and streaming appends are
    exact rank-k refreshes.
    """

    def posterior_cache(self, params, data, y) -> WoodburyCache:
        R, Luu = self._woodbury_root(params, data)
        return build_woodbury_cache(R, y, self.noise(params), Luu)

    def predict_cached(self, params, data, cache, Xstar):
        """Mean/variance from the Woodbury cache — O(s·m²), no solves."""
        Rstar = self._woodbury_root_rows(params, cache.Luu, Xstar)
        return woodbury_predict(cache, Rstar)

    def predict(self, params, data, y, Xstar):
        """Predictive mean/var under the low-rank kernel.

        Routed through :meth:`posterior_cache` — the Woodbury algebra is
        exact for the low-rank kernel, so this *replaces* the per-query CG
        run (mean is bitwise identical between predict and
        predict_cached)."""
        cache = self.posterior_cache(params, data, y)
        return self.predict_cached(params, data, cache, Xstar)

    def update_cache(self, params, data, y, cache, X_new, y_new):
        """Streaming append: exact rank-k Woodbury refresh — zero CG."""
        R_new = self._woodbury_root_rows(params, cache.Luu, X_new)
        return woodbury_update(cache, R_new, jnp.asarray(y_new))
