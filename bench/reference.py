"""Plain dense reference for the exact-GP cells, independent of ``repro``.

Matern-5/2 ARD kernel plus Gaussian noise, in float32 at
``jax.default_matmul_precision("highest")``.  K is built on the device and
inverted in place by the block sweep operator (symmetric block
Gauss-Jordan): each pivot block is the Schur complement of the rows swept
before it, so its Cholesky factor gives log|K| on the way, and after the
last pivot the buffer holds -K^-1.  Only one n x n buffer is ever live, so
the Protein size (n = 45 730, 8.5 GB padded) fits one 16 GB chip.

From -K^-1 the reference gives the exact negative marginal log likelihood,
its exact gradient (trace terms from K^-1 streamed in column panels, not
from probes) and Adam steps.  It imports nothing of the program and takes
nothing the program made.

The control is this reference at a lower precision: its sweep's products
taken in three bf16 passes (``"high"``, the low half of each operand once,
as the TPU's ``Precision.HIGH``), or K and its sweep held in bf16 with
one-pass bf16 products summed in f32 (``"bfloat16"``), written out as
casts so they read the same on any platform.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PANEL = 1024  # sweep block and streaming panel width
SQRT5 = math.sqrt(5.0)
HIGHEST = "highest"  # the reference's precision; the control lowers it
PRECISIONS = ("highest", "high", "bfloat16")


def softplus(x):
    return jnp.logaddexp(x, 0.0)


def inv_softplus(y: float) -> float:
    return float(np.log(np.expm1(y)))


def init_raw(d: int, lengthscale=0.5, outputscale=1.0, noise=0.1) -> dict:
    """Raw (inverse-softplus) hyperparameters of the model at its start."""
    return {
        "raw_lengthscale": jnp.full((d,), inv_softplus(lengthscale), jnp.float32),
        "raw_outputscale": jnp.float32(inv_softplus(outputscale)),
        "raw_noise": jnp.float32(inv_softplus(noise)),
    }


def _r2(Xa, Xb, ls):
    """Squared scaled distances, one input dimension at a time."""
    r2 = 0.0
    for k in range(Xa.shape[1]):
        diff = (Xa[:, k, None] - Xb[None, :, k]) / ls[k]
        r2 = r2 + diff * diff
    return r2


def matern52(Xa, Xb, ls, sf):
    a = SQRT5 * jnp.sqrt(_r2(Xa, Xb, ls))
    return sf * (1.0 + a + a * a / 3.0) * jnp.exp(-a)


def padded(n: int, panel: int = PANEL) -> int:
    return -(-n // panel) * panel


@partial(jax.jit, static_argnames=("n", "panel"))
def _build(Xp, ls, sf, noise, *, n, panel):
    """K + noise*I on the first n rows, identity on the padding."""
    m = Xp.shape[0]
    idx = jnp.arange(m)
    real = idx < n
    K = matern52(Xp, Xp, ls, sf)
    K = jnp.where(real[:, None] & real[None, :], K, 0.0)
    return K + jnp.diag(jnp.where(real, noise, 1.0))


def _mm(a, b, precision: str):
    """a @ b in f32 at ``highest``, else from bf16 halves with f32 sums."""
    if precision == HIGHEST:
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)

    def one_pass(x, y):
        return jnp.matmul(x.astype(jnp.bfloat16), y.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    out = one_pass(a, b)
    if precision == "high":
        lo = lambda x: x - x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        out = out + one_pass(a, lo(b)) + one_pass(lo(a), b)
    return out


def _sweep_block(P):
    """Scalar sweep of one small SPD block: (-P^-1, log|P|).

    Written out (rank-one updates, no LAPACK-style custom call) so that the
    block keeps the big buffer's layout."""
    b = P.shape[0]
    idx = jnp.arange(b)

    def pivot(j, carry):
        P, logdet = carry
        row = jax.lax.dynamic_slice(P, (j, 0), (1, b))[0]
        dj = row[j]
        scaled = jnp.where(idx == j, -1.0 / dj, row / dj)
        P = P - jnp.outer(row, row) / dj
        onj = idx == j
        P = jnp.where(onj[:, None], scaled[None, :], P)
        P = jnp.where(onj[None, :], scaled[:, None], P)
        return P, logdet + jnp.log(dj)

    return jax.lax.fori_loop(0, b, pivot, (P, jnp.float32(0.0)))


@partial(jax.jit, static_argnames=("panel", "precision"), donate_argnums=0)
def _sweep(A, *, panel, precision=HIGHEST):
    """In place: A -> -A^-1, and log|A| from the pivot blocks.

    A stays symmetric through every sweep, so only row panels are read and
    written (a column panel is a row panel transposed): the one n x n
    buffer keeps its layout and is updated in place.  Panels are worked in
    f32 and stored in A's own type (bf16 for the ``"bfloat16"`` control)."""
    m = A.shape[0]
    nb = m // panel
    f32 = jnp.float32

    def pivot(k, carry):
        A, logdet = carry
        Ut = jax.lax.dynamic_slice(A, (k * panel, 0), (panel, m)).astype(f32)  # A[K, :]
        negPinv, ld = _sweep_block(jax.lax.dynamic_slice(Ut, (0, k * panel), (panel, panel)))
        Vt = -_mm(negPinv, Ut, precision)  # (A[:, K] P^-1)'
        Vt_new = jax.lax.dynamic_update_slice(Vt, negPinv, (0, k * panel))

        def rows(i, A):
            Ai = jax.lax.dynamic_slice(A, (i * panel, 0), (panel, m)).astype(f32)
            Vi = jax.lax.dynamic_slice(Vt, (0, i * panel), (panel, panel)).T
            Ai = Ai - _mm(Vi, Ut, precision)
            Ai = jax.lax.dynamic_update_slice(Ai, Vi, (0, k * panel))
            Ai = jnp.where(i == k, Vt_new, Ai)
            return jax.lax.dynamic_update_slice(A, Ai.astype(A.dtype), (i * panel, 0))

        return jax.lax.fori_loop(0, nb, rows, A), logdet + ld

    return jax.lax.fori_loop(0, nb, pivot, (A, f32(0.0)))


@partial(jax.jit, static_argnames=("panel", "precision"))
def _rows_matmul(A, B, *, panel, precision=HIGHEST):
    """A @ B one row panel of A at a time (A is m x m, B is m x q)."""
    m = A.shape[0]

    def body(i, out):
        Ai = jax.lax.dynamic_slice(A, (i * panel, 0), (panel, m))
        return jax.lax.dynamic_update_slice(
            out, _mm(Ai, B, precision), (i * panel, 0))

    return jax.lax.fori_loop(
        0, m // panel, body, jnp.zeros((m, B.shape[1]), jnp.float32))


class Inverse:
    """-K^-1 (padded), log|K| and alpha = K^-1 y for one hyperparameter set,
    the sweep's products at ``precision``."""

    def __init__(self, X, y, raw, panel: int = PANEL, precision: str = HIGHEST):
        self.n, self.d = X.shape
        self.panel = panel
        m = padded(self.n, panel)
        self.Xp = jnp.zeros((m, self.d), jnp.float32).at[: self.n].set(X)
        self.yp = jnp.zeros((m,), jnp.float32).at[: self.n].set(y)
        self.ls = softplus(raw["raw_lengthscale"])
        self.sf = softplus(raw["raw_outputscale"])
        self.noise = softplus(raw["raw_noise"])
        if precision not in PRECISIONS:
            raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
        with jax.default_matmul_precision("highest"):
            A = _build(self.Xp, self.ls, self.sf, self.noise, n=self.n, panel=panel)
            if precision == "bfloat16":
                A = A.astype(jnp.bfloat16)
            self.negKinv, self.logdet = _sweep(A, panel=panel, precision=precision)
            self.alpha = -_rows_matmul(self.negKinv, self.yp[:, None], panel=panel,
                                       precision=precision)[:, 0]

    def loss(self) -> float:
        """-MLL = (y'K^-1 y + log|K| + n log 2 pi) / 2."""
        iq = float(jnp.dot(self.yp, self.alpha, precision=HIGHEST))
        return 0.5 * (iq + float(self.logdet) + self.n * math.log(2 * math.pi))

    def grad(self, raw) -> dict:
        """Exact gradient of -MLL w.r.t. the raw hyperparameters."""
        g_ls, g_sf, g_noise = _grad_terms(
            self.negKinv, self.alpha, self.Xp, self.ls, self.sf,
            n=self.n, panel=self.panel,
        )
        # d softplus(r)/dr = sigmoid(r)
        return {
            "raw_lengthscale": g_ls * jax.nn.sigmoid(raw["raw_lengthscale"]),
            "raw_outputscale": g_sf * jax.nn.sigmoid(raw["raw_outputscale"]),
            "raw_noise": g_noise * jax.nn.sigmoid(raw["raw_noise"]),
        }

    def residual(self) -> float:
        """|K alpha - y| / |y| with K recomputed from X one row panel at a
        time: the reference's own check on its inverse."""
        with jax.default_matmul_precision("highest"):
            Ka = _kernel_rows_matmul(self.Xp, self.alpha, self.ls, self.sf, self.noise,
                                     n=self.n, panel=self.panel)
        return float(jnp.linalg.norm(Ka - self.yp) / jnp.linalg.norm(self.yp))

    def free(self):
        self.negKinv.delete()


@partial(jax.jit, static_argnames=("n", "panel"))
def _kernel_rows_matmul(Xp, v, ls, sf, noise, *, n, panel):
    """(K + noise I) v on the first n rows, K built one row panel at a time."""
    m, d = Xp.shape
    real = jnp.arange(m) < n
    vr = jnp.where(real, v, 0.0)

    def body(i, out):
        Xi = jax.lax.dynamic_slice(Xp, (i * panel, 0), (panel, d))
        Ki = matern52(Xi, Xp, ls, sf)
        row = jnp.matmul(Ki, vr, precision=HIGHEST)
        return jax.lax.dynamic_update_slice(out, row, (i * panel,))

    out = jax.lax.fori_loop(0, m // panel, body, jnp.zeros((m,), jnp.float32))
    return jnp.where(real, out + noise * v, 0.0)


@partial(jax.jit, static_argnames=("n", "panel"))
def _grad_terms(negKinv, alpha, Xp, ls, sf, *, n, panel):
    """0.5 * sum_ij (K^-1 - alpha alpha')_ij dK_ij for every hyperparameter,
    with K^-1 read one column panel at a time.

    dK/dsf = K_f / sf,  dK/dnoise = I,
    dK/dls_k = sf * 5/3 * (1 + a) e^-a * (x_ik - x_jk)^2 / ls_k^3,  a = sqrt5 r.
    """
    m, d = Xp.shape
    real = jnp.arange(m) < n

    def body(j, acc):
        g_ls, g_sf, g_noise = acc
        C = -jax.lax.dynamic_slice(negKinv, (j * panel, 0), (panel, m)).T
        Xj = jax.lax.dynamic_slice(Xp, (j * panel, 0), (panel, d))
        aj = jax.lax.dynamic_slice(alpha, (j * panel,), (panel,))
        rj = jax.lax.dynamic_slice(real, (j * panel,), (panel,))
        mask = real[:, None] & rj[None, :]
        W = jnp.where(mask, C - alpha[:, None] * aj[None, :], 0.0)
        a = SQRT5 * jnp.sqrt(_r2(Xp, Xj, ls))
        e = jnp.exp(-a)
        g_sf = g_sf + jnp.sum(W * (1.0 + a + a * a / 3.0) * e)
        G = W * (sf * 5.0 / 3.0) * (1.0 + a) * e
        g_ls = g_ls + jnp.stack([
            jnp.sum(G * (Xp[:, k, None] - Xj[None, :, k]) ** 2) for k in range(d)
        ]) / ls**3
        rows = j * panel + jnp.arange(panel)
        diag = W[rows, jnp.arange(panel)]
        g_noise = g_noise + jnp.sum(jnp.where(rj, diag, 0.0))
        return g_ls, g_sf, g_noise

    zero = jnp.float32(0.0)
    g_ls, g_sf, g_noise = jax.lax.fori_loop(
        0, m // panel, body, (jnp.zeros((d,), jnp.float32), zero, zero)
    )
    return 0.5 * g_ls, 0.5 * g_sf, 0.5 * g_noise


def adam(lr: float, b1=0.9, b2=0.999, eps=1e-8):
    """Textbook Adam over a dict of arrays: returns (init, update)."""

    def init(params):
        zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
        return {"t": 0, "m": zeros, "v": dict(zeros)}

    def update(grads, state, params):
        t = state["t"] + 1
        m = {k: b1 * state["m"][k] + (1 - b1) * g for k, g in grads.items()}
        v = {k: b2 * state["v"][k] + (1 - b2) * g * g for k, g in grads.items()}
        new = {
            k: params[k] - lr * (m[k] / (1 - b1**t)) / (jnp.sqrt(v[k] / (1 - b2**t)) + eps)
            for k in params
        }
        return new, {"t": t, "m": m, "v": v}

    return init, update


def train_trajectory(X, y, lr: float, steps: int, *, loss_scale: float = 1.0,
                     precision: str = HIGHEST):
    """The reference's own first ``steps`` Adam steps from the model's start:
    losses at each step, the first gradient, and the parameters after the
    last step.  ``loss_scale`` multiplies loss and gradient (the half-batch
    fault reading uses 2); ``precision`` is the sweep's matmul precision
    (the control lowers it)."""
    raw = init_raw(X.shape[1])
    init, update = adam(lr)
    state = init(raw)
    losses, residuals, first_grad, start = [], [], None, raw
    for _ in range(steps):
        inv = Inverse(X, y, raw, precision=precision)
        losses.append(loss_scale * inv.loss())
        residuals.append(inv.residual())
        g = {k: loss_scale * v for k, v in inv.grad(raw).items()}
        inv.free()
        del inv
        if first_grad is None:
            first_grad = g
        raw, state = update(g, state, raw)
    return {"losses": losses, "first_grad": first_grad, "start": start, "end": raw,
            "residuals": residuals}
