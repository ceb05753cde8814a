"""SKI / KISS-GP through BBMM (paper §5), with an optional learned feature map.

K̂ ≈ W K_UU Wᵀ + σ²I with
  * W — sparse cubic-convolution interpolation weights (4 taps per dim,
    Keys 1981),
  * K_UU — kernel on a regular grid: a (Kronecker product of) symmetric
    Toeplitz matrices, multiplied via FFT circulant embedding in
    O(m log m) per column.

Total blackbox-matmul cost: O(t·n·4^d + t·m log m) — the paper's headline
SKI complexity.  Multi-dimensional grids use the separable (product-kernel)
form of the RBF kernel, the standard KISS-GP construction.

On raw inputs the grid is fitted to the data and W computed once, in
``prepare_inputs``.  With ``features=FeatureMap(...)`` (deep kernel
learning, GPyTorch's DKL example: feature net → ScaleToBounds → grid
interpolation → Kronecker-Toeplitz K_UU) the grid is static over the
feature map's bounds and W is rebuilt from the features inside every
traced ``operator()``: the interpolation weights are differentiable in the
features, so the MLL gradient reaches the network.  The device ops carry
the named scopes ``dkl.features`` (network and ScaleToBounds),
``ski.interp`` (building W, Wᵀ·M, W·V) and ``ski.grid`` (the
Kronecker-Toeplitz product).
"""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from repro.core import (
    AddedDiagOperator,
    BBMMSettings,
    InterpolatedOperator,
    KroneckerOperator,
    ScaledOperator,
    ToeplitzOperator,
    build_posterior_cache,
    cached_inv_quad,
    cached_mean,
    marginal_log_likelihood,
    solve as bbmm_solve,
)
from .dkl import FeatureMap, scale_to_bounds
from .exact import _softplus, _inv_softplus
from .training import fit_gp


def _cubic_weights(u):
    """Keys cubic-convolution weights for frac u ∈ [0,1) at taps
    (-1, 0, 1, 2) relative to the left grid point (a = −0.5)."""
    a = -0.5
    s0 = u + 1.0  # distance to tap -1, in (1, 2)
    s1 = u  # tap 0, in [0, 1)
    s2 = 1.0 - u  # tap 1
    s3 = 2.0 - u  # tap 2, in (1, 2]

    def inner(s):
        return ((a + 2.0) * s - (a + 3.0)) * s * s + 1.0

    def outer(s):
        return ((a * s - 5.0 * a) * s + 8.0 * a) * s - 4.0 * a

    return jnp.stack([outer(s0), inner(s1), inner(s2), outer(s3)], axis=-1)


@dataclasses.dataclass(frozen=True)
class Grid:
    """Regular per-dimension grid with precomputed interpolation structure."""

    mins: jax.Array  # (d,)
    steps: jax.Array  # (d,)
    sizes: tuple  # static per-dim sizes

    @staticmethod
    def fit(X, sizes):
        pad = 3  # room for the cubic stencil at the borders
        mins = X.min(0)
        maxs = X.max(0)
        steps = (maxs - mins) / (jnp.array([s - 1 - 2 * pad for s in sizes]))
        return Grid(mins - pad * steps, steps, tuple(sizes))

    @staticmethod
    def over_bounds(bounds, sizes):
        """A static grid over ``bounds`` in every dimension, extended past
        each end as GPyTorch's ``create_grid`` does: ``size`` points from
        lo − h to hi + h, h = (hi − lo) / (size − 2)."""
        lo, hi = bounds
        h = [(hi - lo) / (s - 2) for s in sizes]
        mins = jnp.asarray([lo - hd for hd in h], jnp.float32)
        steps = jnp.asarray([(hi - lo + 2 * hd) / (s - 1) for hd, s in zip(h, sizes)],
                            jnp.float32)
        return Grid(mins, steps, tuple(sizes))

    def points(self, dim):
        return self.mins[dim] + self.steps[dim] * jnp.arange(self.sizes[dim])

    def interpolate(self, X):
        """Sparse W: (indices, values) each (n, 4^d).  Every row's taps are
        consecutive in each dimension and inside the grid: all rows share
        one stencil, as ``InterpolatedOperator`` requires."""
        n, d = X.shape
        idx_list, w_list = [], []
        for dim in range(d):
            pos = (X[:, dim] - self.mins[dim]) / self.steps[dim]
            left = jnp.clip(jnp.floor(pos).astype(jnp.int32), 1, self.sizes[dim] - 3)
            u = pos - left
            w = _cubic_weights(u)  # (n, 4)
            taps = left[:, None] + jnp.arange(-1, 3)[None, :]  # (n, 4)
            idx_list.append(taps)
            w_list.append(w)

        # tensor-product combination across dims → flat grid indices
        indices = idx_list[0]
        values = w_list[0]
        stride = self.sizes[0]
        for dim in range(1, d):
            indices = (
                indices[:, :, None] * self.sizes[dim] + idx_list[dim][:, None, :]
            ).reshape(n, -1)
            values = (values[:, :, None] * w_list[dim][:, None, :]).reshape(n, -1)
        return indices, values


@dataclasses.dataclass
class SKI:
    grid_size: int = 100  # per dimension
    kernel_type: str = "rbf"
    settings: BBMMSettings = dataclasses.field(default_factory=BBMMSettings)
    # "highest" | "mixed": accepted for API uniformity with ExactGP/SGPR.
    # SKI's heavy stage is the FFT Toeplitz matmul, whose circulant
    # embedding is numerically unsafe at bf16, so the operator keeps its
    # contractions f32 (with_compute_dtype no-ops on Toeplitz) — mixed only
    # engages the mBCG residual-refresh machinery.  None follows
    # settings.precision; an explicit value overrides it unconditionally.
    precision: str | None = None
    # fused-CG knob (API uniformity): the interpolated Toeplitz operator
    # has no fused kernel — True falls back to the unfused loop.  None
    # follows ``settings.fuse_cg``.
    fuse_cg: bool | None = None
    # learned feature map (deep kernel learning): None interpolates the raw
    # inputs on a grid fitted to them; a FeatureMap interpolates its scaled
    # output on a static grid_size^dims grid over its bounds
    features: FeatureMap | None = None

    def __post_init__(self):
        if self.precision is not None:
            self.settings = dataclasses.replace(
                self.settings, precision=self.precision
            )
        if self.fuse_cg is not None:
            self.settings = dataclasses.replace(self.settings, fuse_cg=self.fuse_cg)

    def init_params(self, X, key=None):
        d = X.shape[1] if self.features is None else self.features.dims
        params = {
            "raw_lengthscale": jnp.zeros((d,)) + _inv_softplus(jnp.float32(0.5)),
            "raw_outputscale": _inv_softplus(jnp.float32(1.0)),
            "raw_noise": _inv_softplus(jnp.float32(0.1)),
        }
        if self.features is not None:
            key = jax.random.PRNGKey(7) if key is None else key
            params["net"] = self.features.init(key, X.shape[1])
        return params

    def prepare_inputs(self, X):
        """Precompute geometry (grid + W) — independent of hyperparameters.

        This is SKI's ``data`` in the GPModel protocol: every downstream
        method takes this geometry dict where other models take X.  With a
        feature map W depends on the network, so the data is X itself."""
        if self.features is not None:
            return X
        d = X.shape[1]
        grid = Grid.fit(X, (self.grid_size,) * d)
        indices, values = grid.interpolate(X)
        return {"grid": grid, "indices": indices, "values": values}

    # historical name, kept for direct call sites
    prepare = prepare_inputs

    def _kuu(self, params, grid: Grid):
        """Kronecker-of-Toeplitz K_UU (separable RBF across dims)."""
        ell = _softplus(params["raw_lengthscale"])
        out = _softplus(params["raw_outputscale"])
        factors = []
        d = len(grid.sizes)
        for dim in range(d):
            pts = grid.points(dim)
            col = jnp.exp(-0.5 * ((pts - pts[0]) / ell[dim]) ** 2)
            if dim == 0:
                col = col * out
            factors.append(ToeplitzOperator(col))
        if d == 1:
            return factors[0]
        return KroneckerOperator(tuple(factors))

    def _geometry(self, params, data):
        """(grid, indices, values, place): the grid, the training rows'
        interpolation weights, and the map that puts new inputs where the
        grid sees them (the identity on raw inputs; with a feature map, the
        network and the training features' ScaleToBounds)."""
        if self.features is None:
            return data["grid"], data["indices"], data["values"], lambda Xs: Xs
        fm = self.features
        grid = Grid.over_bounds(fm.bounds, (self.grid_size,) * fm.dims)
        # the network follows the precision policy: under "highest" its f32
        # matmuls take full f32 precision (one bf16 pass is the TPU default)
        policy = (jax.default_matmul_precision("highest")
                  if self.settings.precision == "highest" else contextlib.nullcontext())
        with jax.named_scope("dkl.features"), policy:
            Z = fm(params["net"], data)
            lo, hi = jnp.min(Z), jnp.max(Z)
            Z = scale_to_bounds(Z, lo, hi, fm.bounds)
        with jax.named_scope("ski.interp"):
            indices, values = grid.interpolate(Z)

        def place(Xs):
            # as ScaleToBounds in eval mode: the training features' min and
            # max, new features clamped to them
            with jax.named_scope("dkl.features"), policy:
                return scale_to_bounds(jnp.clip(fm(params["net"], Xs), lo, hi), lo, hi, fm.bounds)

        return grid, indices, values, place

    def operator(self, params, data):
        grid, indices, values, _ = self._geometry(params, data)
        base = InterpolatedOperator(
            indices=indices, values=values, base=self._kuu(params, grid)
        )
        return AddedDiagOperator(base, _softplus(params["raw_noise"]))

    def loss(self, params, geom, y, key):
        return -marginal_log_likelihood(self.operator(params, geom), y, key, self.settings)

    def noise(self, params):
        return _softplus(params["raw_noise"])

    def fit(self, X, y, *, steps=100, lr=0.1, key=None, verbose=False):
        """(params, history) via the shared driver.  The geometry the loop
        used is reproducible as ``self.prepare_inputs(X)`` (deterministic
        in X) — fit no longer returns it, per the GPModel protocol."""
        key = jax.random.PRNGKey(2) if key is None else key
        return fit_gp(self, X, y, steps=steps, lr=lr, key=key, verbose=verbose)

    def _cross(self, params, geom, Xstar):
        """SKI cross-covariance machinery for a test block: returns
        (KXs (n, s), kss (s,)) — k(x*, X) ≈ W* K_UU Wᵀ interpolated on the
        same grid as training."""
        grid, indices, values, place = self._geometry(params, geom)
        kuu = self._kuu(params, grid)
        s_idx, s_val = grid.interpolate(place(Xstar))
        star_op = InterpolatedOperator(indices=s_idx, values=s_val, base=kuu)
        train_op = InterpolatedOperator(indices=indices, values=values, base=kuu)
        KXs = train_op._W_matmul(
            kuu.matmul(star_op._Wt_matmul(jnp.eye(Xstar.shape[0])))
        )
        return KXs, star_op.diagonal()

    def posterior_cache(self, params, geom, y, *, key=None, variance_cache=True):
        """One engine call → :class:`repro.core.PosteriorCache` over the SKI
        operator (fixed default key ⇒ deterministic rebuilds, and
        ``predict`` shares this exact path for its mean)."""
        key = jax.random.PRNGKey(0) if key is None else key
        return build_posterior_cache(
            self.operator(params, geom), y, key, self.settings,
            variance_cache=variance_cache,
        )

    def predict_cached(self, params, geom, cache, Xstar):
        """Serve SKI mean/variance from the cache — zero CG iterations:
        O(s·4^d + m log m) interpolation + O(n·m) Rayleigh–Ritz variance."""
        KXs, kss = self._cross(params, geom, Xstar)
        mean = cached_mean(cache, KXs)
        var = kss - cached_inv_quad(cache, KXs)
        return mean, jnp.clip(var, 1e-8) + _softplus(params["raw_noise"])

    def predict(self, params, geom, y, Xstar, *, key=None):
        """SKI predictive mean/var: cross-covariances interpolate the same
        grid (k(x*, X) ≈ w*ᵀ K_UU Wᵀ).  Mean comes from the posterior cache
        (bitwise identical to ``predict_cached``); variance runs exact mBCG
        solves against k_X*."""
        cache = self.posterior_cache(params, geom, y, key=key, variance_cache=False)
        op = self.operator(params, geom)
        KXs, kss = self._cross(params, geom, Xstar)
        mean = cached_mean(cache, KXs)
        # variance: exact solves, reusing the cache's preconditioner factors
        solves = bbmm_solve(op, KXs, self.settings, precond=cache.precond)
        var = kss - jnp.sum(KXs * solves, axis=0)
        return mean, jnp.clip(var, 1e-8) + _softplus(params["raw_noise"])
