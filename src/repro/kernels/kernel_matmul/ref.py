"""Pure-jnp oracle for the fused kernel matmul."""

import jax.numpy as jnp


def kernel_matmul_ref(
    X, M, lengthscale, outputscale, sigma2, *, kernel_type="rbf", rows=None
):
    """(K(X,X) + σ²I) @ M, materialized — the correctness reference.

    ``rows`` (an index array) keeps only those output rows, materializing
    the (len(rows), n) block of K instead of all of it.  Distances are sums
    of squared differences: no ‖x‖² + ‖x'‖² − 2⟨x, x'⟩ cancellation, whose
    ulp of ‖x‖² on the diagonal Matérn-1/2's sqrt would lift to ~1e-3."""
    Xs = X / lengthscale
    r = jnp.arange(X.shape[0]) if rows is None else jnp.asarray(rows)
    Xr = Xs[r]
    d2 = jnp.sum(jnp.square(Xr[:, None, :] - Xs[None, :, :]), -1)
    if kernel_type == "rbf":
        K = outputscale * jnp.exp(-0.5 * d2)
    else:
        d = jnp.sqrt(jnp.maximum(d2, 1e-20))
        if kernel_type == "matern12":
            K = outputscale * jnp.exp(-d)
        elif kernel_type == "matern32":
            a = jnp.sqrt(3.0) * d
            K = outputscale * (1.0 + a) * jnp.exp(-a)
        elif kernel_type == "matern52":
            a = jnp.sqrt(5.0) * d
            K = outputscale * (1.0 + a + a * a / 3.0) * jnp.exp(-a)
        else:
            raise ValueError(kernel_type)
    K = K + sigma2 * (r[:, None] == jnp.arange(X.shape[0])[None, :]).astype(K.dtype)
    return (K @ M.astype(K.dtype)).astype(jnp.float32)
