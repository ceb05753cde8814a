"""Stochastic Lanczos quadrature for log-determinants (paper Eq. 5–6).

Given the per-probe tridiagonal matrices T̃_i recovered by mBCG, the Gauss
quadrature value e₁ᵀ log(T̃_i) e₁ estimates ẑᵢᵀ log(Ã) ẑᵢ for the
*normalized, preconditioned* probe ẑᵢ = P̂^{-1/2}zᵢ/‖P̂^{-1/2}zᵢ‖ and
Ã = P̂^{-1/2} K̂ P̂^{-1/2}.  With probes drawn from N(0, P̂):

    log|P̂⁻¹K̂| = Tr(log Ã) ≈ (1/t) Σᵢ (zᵢᵀP̂⁻¹zᵢ) · e₁ᵀ log(T̃_i) e₁
    log|K̂|     = log|P̂⁻¹K̂| + log|P̂|              (paper §4.1)

T̃ eigen-decomposition is exact and cheap: the matrices are p×p (p ≈ 10–100),
decomposed with a batched dense ``eigh`` (the tridiagonal structure makes
this numerically benign).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .mbcg import MBCGResult, tridiag_matrices


def slq_quadrature(T: jax.Array, fn=jnp.log, eig_floor: float = 1e-10) -> jax.Array:
    """e₁ᵀ f(T̃_i) e₁ for a stack of (..., t, p, p) symmetric tridiagonal
    matrices (leading batch dims broadcast).

    Returns (..., t) quadrature values.
    """
    evals, evecs = jnp.linalg.eigh(T)
    evals = jnp.clip(evals, eig_floor)  # PSD guard — tiny negative from roundoff
    first_row = evecs[..., 0, :]  # (..., t, p)   e₁ᵀV
    return jnp.sum(first_row**2 * fn(evals), axis=-1)


def logdet_from_mbcg(
    result: MBCGResult,
    probe_inv_quads: jax.Array,
    precond_logdet: jax.Array,
) -> jax.Array:
    """Assemble the log|K̂| estimate from an mBCG call on probe columns.

    Args:
      result: mBCG output for the probe RHS block (columns are the zᵢ).
      probe_inv_quads: (t,) values zᵢᵀP̂⁻¹zᵢ (≡ ‖zᵢ‖² when unpreconditioned).
      precond_logdet: log|P̂| (0 when unpreconditioned).
    """
    with jax.named_scope("bbmm.logdet"):
        T = tridiag_matrices(result)
        quad = slq_quadrature(T)  # (..., t)
        est = jnp.mean(probe_inv_quads * quad, axis=-1)
        return est + precond_logdet
