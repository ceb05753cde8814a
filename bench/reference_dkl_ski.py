"""Plain dense reference for the deep-kernel KISS-GP cell, independent of ``repro``.

The model of GPyTorch's DKL example (Wilson et al. 2016, arXiv:1511.02222),
written out in ``jax.numpy``, f32, at ``jax.default_matmul_precision("highest")``:

  z  = W4 relu(W3 relu(W2 relu(W1 x + b1) + b2) + b3) + b4       (ReLU MLP)
  z~ = (z - min z) * 0.95 (hi - lo) / (max z - min z) + 0.95 lo   (ScaleToBounds,
       one global min and max over the training features)
  W  = dense (n x G^D) interpolation matrix: row i is the outer product over
       grid dimensions of k(pos_id - g), g = 0..G-1, with pos_id the point's
       position in grid spacings and k the Keys cubic kernel (a = -0.5;
       zero beyond two spacings)
  K^ = W (sf^2 K_1 x K_2) W' + s2 I,  K_d[a, b] = exp(-((a - b) h / l_d)^2 / 2)
  -MLL = (y' K^-1 y + log|K^| + n log 2 pi) / 2

The grid is static: G points per dimension from lo - e to hi + e, e = (hi -
lo) / (G - 2), as GPyTorch's ``create_grid`` extends it.  K^ is built
dense on the device (W is n x G^D, 0.66 GB at the cell's size) and inverted
in place by ``bench/reference.py``'s block sweep.  The gradient of -MLL is
one VJP of params -> K^(params) at (K^-1 - alpha alpha') / 2, alpha =
K^-1 y: the trace term and the y-term together, exact, no probes.

The control is this reference at a lower precision: every product (the
network's layers, W K_UU W' and the sweep) from bf16 operands with f32
sums (``"bfloat16"``), or three bf16 passes (``"high"``), written out as
casts so they read the same on any platform.  ``net_precision`` lowers
the network's layers alone and keeps the rest at ``highest``: the error
of a feature net run at one bf16 pass, with the solve left exact.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import data
from bench.reference import HIGHEST, PANEL, PRECISIONS, _mm, _rows_matmul, _sweep, adam, padded

CUBIC_A = -0.5  # Keys' cubic convolution parameter
SCALE = 0.95  # ScaleToBounds keeps the features inside 0.95 of the bounds
INIT_STREAM = 1  # the seeded generator's stream for the starting weights


def init_params(d: int, widths, seed: int) -> dict:
    """The model's start, from ``seed``: the network as torch's ``nn.Linear``
    default draws it (weights and biases uniform on +-1/sqrt(fan_in)), raw
    hyperparameters 0 (GPyTorch's defaults: lengthscales, outputscale and
    noise softplus(0) = log 2).  Flat names: ``net.<layer>.w`` (fan_in x
    fan_out), ``net.<layer>.b``, ``raw_lengthscale`` (one per grid
    dimension), ``raw_outputscale``, ``raw_noise``."""
    g = data.rng(seed, INIT_STREAM)
    out = {}
    for i, (a, b) in enumerate(zip((d, *widths[:-1]), widths)):
        bound = 1.0 / math.sqrt(a)
        out[f"net.{i}.w"] = g.uniform(-bound, bound, (a, b)).astype(np.float32)
        out[f"net.{i}.b"] = g.uniform(-bound, bound, (b,)).astype(np.float32)
    out["raw_lengthscale"] = np.zeros((widths[-1],), np.float32)
    out["raw_outputscale"] = np.float32(0.0)
    out["raw_noise"] = np.float32(0.0)
    return out


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def layers(params) -> int:
    return sum(1 for k in params if k.startswith("net.") and k.endswith(".w"))


def features(params, X, bounds, precision=HIGHEST):
    """The network, then ScaleToBounds with the min and max of these rows."""
    h = X
    L = layers(params)
    for i in range(L):
        h = _mm(h, params[f"net.{i}.w"], precision) + params[f"net.{i}.b"]
        if i < L - 1:
            h = jnp.maximum(h, 0.0)
    lo, hi = bounds
    zmin, zmax = jnp.min(h), jnp.max(h)
    return (h - zmin) * (SCALE * (hi - lo) / (zmax - zmin)) + SCALE * lo


def grid_axis(bounds, size: int) -> tuple[float, float]:
    """(first point, spacing) of one grid dimension."""
    lo, hi = bounds
    e = (hi - lo) / (size - 2)
    return lo - e, (hi - lo + 2 * e) / (size - 1)


def cubic(s):
    """Keys' cubic convolution kernel at distance s (in grid spacings)."""
    a = CUBIC_A
    s = jnp.abs(s)
    near = ((a + 2.0) * s - (a + 3.0)) * s * s + 1.0
    far = ((a * s - 5.0 * a) * s + 8.0 * a) * s - 4.0 * a
    return jnp.where(s <= 1.0, near, jnp.where(s < 2.0, far, 0.0))


def interpolation(Z, bounds, size: int):
    """Dense W (n x size^D), row-major over the grid dimensions."""
    first, step = grid_axis(bounds, size)
    g = jnp.arange(size, dtype=jnp.float32)
    W = None
    for k in range(Z.shape[1]):
        Wk = cubic((Z[:, k, None] - first) / step - g[None, :])  # (n, size)
        W = Wk if W is None else (W[:, :, None] * Wk[:, None, :]).reshape(Z.shape[0], -1)
    return W


def grid_kernel(params, bounds, size: int):
    """Dense sf^2 K_1 x K_2 x ... on the grid."""
    _, step = grid_axis(bounds, size)
    ls = softplus(params["raw_lengthscale"])
    sf = softplus(params["raw_outputscale"])
    diff = (jnp.arange(size)[:, None] - jnp.arange(size)[None, :]).astype(jnp.float32) * step
    K = None
    for k in range(ls.shape[0]):
        Kk = jnp.exp(-0.5 * (diff / ls[k]) ** 2)
        K = Kk if K is None else jnp.kron(K, Kk)
    return sf * K


def khat(params, X, *, bounds, size, precision=HIGHEST, net_precision=None):
    """K^ = W K_UU W' + noise I (n x n); the network at ``net_precision``
    (``precision`` if None)."""
    Z = features(params, X, bounds, net_precision or precision)
    W = interpolation(Z, bounds, size)
    Kuu = grid_kernel(params, bounds, size)
    K = _mm(_mm(W, Kuu, precision), W.T, precision)
    return K + softplus(params["raw_noise"]) * jnp.eye(X.shape[0], dtype=jnp.float32)


@partial(jax.jit, static_argnames=("bounds", "size", "panel", "precision", "net_precision"))
def _build(params, X, *, bounds, size, panel, precision, net_precision):
    """K^ on the first n rows of a padded buffer, identity on the padding."""
    n = X.shape[0]
    m = padded(n, panel)
    K = khat(params, X, bounds=bounds, size=size, precision=precision,
             net_precision=net_precision)
    A = jnp.eye(m, dtype=jnp.float32).at[:n, :n].set(K)
    return A.astype(jnp.bfloat16) if precision == "bfloat16" else A


@partial(jax.jit, static_argnames=("bounds", "size", "precision", "net_precision"))
def _grad(params, X, negKinv, alpha, *, bounds, size, precision, net_precision):
    """VJP of params -> K^(params) at (K^-1 - alpha alpha') / 2."""
    n = X.shape[0]
    G = 0.5 * (-negKinv[:n, :n].astype(jnp.float32) - alpha[:, None] * alpha[None, :])
    _, vjp = jax.vjp(lambda p: khat(p, X, bounds=bounds, size=size, precision=precision,
                                    net_precision=net_precision), params)
    return vjp(G)[0]


@partial(jax.jit, static_argnames=("bounds", "size", "net_precision"))
def _khat_matvec(params, X, v, *, bounds, size, net_precision):
    """K^ v from W, K_UU and W' in turn (no n x n matrix)."""
    W = interpolation(features(params, X, bounds, net_precision), bounds, size)
    Kuu = grid_kernel(params, bounds, size)
    return W @ (Kuu @ (W.T @ v)) + softplus(params["raw_noise"]) * v


class Inverse:
    """-K^-1 (padded), log|K^| and alpha = K^-1 y for one parameter set,
    every product at ``precision``, the network's at ``net_precision``
    (``precision`` if None)."""

    def __init__(self, X, y, params, *, bounds, size, panel: int = PANEL,
                 precision: str = HIGHEST, net_precision: str | None = None):
        for p in (precision, net_precision or precision):
            if p not in PRECISIONS:
                raise ValueError(f"precision must be one of {PRECISIONS}, got {p!r}")
        self.net_precision = net_precision or precision
        self.n = X.shape[0]
        self.X, self.y, self.params = jnp.asarray(X), jnp.asarray(y), params
        self.bounds, self.size, self.panel, self.precision = bounds, size, panel, precision
        self.yp = jnp.zeros((padded(self.n, panel),), jnp.float32).at[: self.n].set(self.y)
        with jax.default_matmul_precision("highest"):
            A = _build(params, self.X, bounds=bounds, size=size, panel=panel,
                       precision=precision, net_precision=self.net_precision)
            self.negKinv, self.logdet = _sweep(A, panel=panel, precision=precision)
            self.alpha = -_rows_matmul(self.negKinv, self.yp[:, None], panel=panel,
                                       precision=precision)[: self.n, 0]

    def loss(self) -> float:
        """-MLL = (y'K^-1 y + log|K^| + n log 2 pi) / 2."""
        iq = float(jnp.dot(self.y, self.alpha, precision=HIGHEST))
        return 0.5 * (iq + float(self.logdet) + self.n * math.log(2 * math.pi))

    def grad(self) -> dict:
        with jax.default_matmul_precision("highest"):
            return _grad(self.params, self.X, self.negKinv, self.alpha, bounds=self.bounds,
                         size=self.size, precision=self.precision,
                         net_precision=self.net_precision)

    def residual(self) -> float:
        """|K^ alpha - y| / |y|, K^ applied factor by factor in f32 (from
        the features this inverse was built on): the reference's own check
        on its inverse."""
        with jax.default_matmul_precision("highest"):
            Ka = _khat_matvec(self.params, self.X, self.alpha, bounds=self.bounds,
                              size=self.size, net_precision=self.net_precision)
        return float(jnp.linalg.norm(Ka - self.y) / jnp.linalg.norm(self.y))

    def free(self):
        self.negKinv.delete()


def train_trajectory(X, y, start: dict, lr: float, steps: int, *, bounds, size: int,
                     loss_scale: float = 1.0, precision: str = HIGHEST,
                     net_precision: str | None = None):
    """The reference's own first ``steps`` Adam steps from ``start``: losses
    at each step, the first gradient, and the parameters after the last
    step.  ``loss_scale`` multiplies loss and gradient (the half-batch
    fault reading uses 2); ``precision`` lowers every product and
    ``net_precision`` the network's alone (the controls)."""
    params = {k: jnp.asarray(v) for k, v in start.items()}
    init, update = adam(lr)
    state = init(params)
    losses, residuals, first_grad = [], [], None
    bounds = tuple(float(b) for b in bounds)
    for _ in range(steps):
        inv = Inverse(X, y, params, bounds=bounds, size=size, precision=precision,
                      net_precision=net_precision)
        losses.append(loss_scale * inv.loss())
        residuals.append(inv.residual())
        g = {k: loss_scale * v for k, v in inv.grad().items()}
        inv.free()
        del inv
        if first_grad is None:
            first_grad = g
        params, state = update(g, state, params)
    return {"losses": losses, "first_grad": first_grad, "start": dict(start), "end": params,
            "residuals": residuals}
