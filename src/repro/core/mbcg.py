"""mBCG — modified Batched Conjugate Gradients (paper Algorithm 2).

One batched matmul against K̂ per iteration drives *all* GP inference
quantities:

  * solves  U = K̂⁻¹ B   for a whole block of right-hand sides at once, and
  * the Lanczos tridiagonalization T̃_i of (the preconditioned) K̂ w.r.t.
    each probe column — recovered *for free* from the CG coefficients
    (Saad 2003, §6.7.3; paper Observation 3) so the numerically fragile
    Lanczos recurrence is never run.

Batching: ``B`` may carry arbitrary *leading* batch dimensions —
``(n, t)``, ``(b, n, t)``, ``(b1, b2, n, t)`` — and every reduction runs
over ``axis=-2`` (the n rows), so one ``lax.scan`` drives all problems of
a multi-restart hyperparameter search / multi-output GP simultaneously:
the per-iteration work is ONE fused matmul of shape ``(b, n, t)`` instead
of a Python loop of ``b`` engine calls.  ``matmul`` must accept the same
leading batch dims (dense operators broadcast for free under ``@``).

TPU adaptation: data-dependent termination is replaced by a fixed-trip
``lax.scan`` with per-(batch, column) convergence *masking* — converged
columns stop updating (α forced to 0) and their tridiagonal blocks are
padded with identity, which leaves the Gauss quadrature value
e₁ᵀlog(T̃)e₁ exactly unchanged.  This keeps the program static-shaped for
pjit/SPMD while preserving CG's tolerance semantics.

Mixed-precision adaptation: when ``matmul`` runs at reduced precision
(bf16 kernel tiles), the *recursively updated* residual drifts away from
the true residual b − K̂u — CG can report convergence it never achieved,
or stall above a tolerance it could reach.  ``refresh_every`` installs a
periodic **f32 residual refresh** (residual replacement in the spirit of
Van der Vorst & Ye 1999): every ``refresh_every`` steps the true residual
is recomputed through ``refresh_matmul`` (a full-precision matmul of the
same operator) and the per-column masking state is *re-derived* from it —
columns whose recursive residual lied are reactivated, columns genuinely
below ``tol`` freeze.  Three guards make the scheme safe at any
conditioning, all per column:

  * **curvature guard** — bf16 noise can round the effective operator
    indefinite, making dᵀK̂d ≤ 0 and α a garbage (often huge) step; such
    steps are skipped and the direction restarts at the next refresh;
  * **momentum keep/restart** — the CG direction is kept (β against the
    refreshed residual) while the recursive residual still *agrees* with
    the true one (relative drift < 25%), preserving the superlinear
    convergence a hard restart would destroy; once the recursion has
    drifted, the direction restarts from the preconditioned true residual;
  * **best-solution snapshot** — the best refreshed iterate per column is
    tracked (and a non-finite trajectory is rescued from it), and the
    returned solve/residual is that best iterate: reduced precision can
    stall short of ``tol`` (the honest outcome when κ·ε_bf16 ≳ 1), but the
    reported answer never diverges.

This keeps ``tol`` semantics honest under bf16 matmul noise; the f32
matmul is paid once per ``refresh_every`` iterations.  Refresh steps break
the CG three-term recurrence, so the recovered tridiagonals (and hence the
SLQ log-det) are perturbed — the benchmark suite's tolerance study
quantifies the resulting MLL error.

**Adaptive refresh period** (``refresh_adaptive=True``): the static
default period pays an f32 matmul every ``refresh_every`` steps even when
the bf16 recursion is tracking the truth closely.  The adaptive policy
uses the drift measurement each refresh already computes: while the
maximum per-column drift stays below ``REFRESH_DRIFT_GATE`` the period
*doubles* (geometric stretch, capped at ``refresh_max_period``), and on a
violation it snaps straight back to the base ``refresh_every`` — so a
well-conditioned solve pays O(log p) refreshes instead of p/period, while
an ill-conditioned one degenerates to the honest static schedule.  The
count of f32 refreshes actually taken is reported as
``MBCGResult.num_refreshes``.

Note on Algorithm 2 as printed in the paper: its β update uses
(z_j∘z_j)/(z_{j-1}∘z_{j-1}); the textbook PCG recurrence (and GPyTorch's
implementation) uses r·z in both places.  We implement the standard PCG
update — it is the one for which Observation 3 (tridiag recovery) holds.

**Fused CG step** (``fused_step``): operators that can execute a whole CG
iteration inside their kernel (the Pallas kernel-matmul family — see
``repro.kernels.kernel_matmul``) advertise a :data:`CGStepFn` via
``LinearOperator.fused_cg_step_fn()``.  When one is passed, the loop body
becomes ONE fused launch per iteration: the step applies the pending
per-column (α, β, γ) state updates, computes V = K̂·D and returns the
four per-column reductions

    dᵀV  (α denominator),   rᵀr  (rz, measured exactly),
    rᵀV, vᵀV               (the pipelined rz recurrence
                            rz' = rz − 2α·rᵀV + α²·vᵀV)

so only O(t) scalar arithmetic — α, β, the convergence masks — remains in
XLA between launches.  Because β for the *next* direction must be formed
before the next launch measures the next rᵀr, it uses the pipelined-CG
recurrence (Ghysels & Vanroose 2014) — the one place the fused path's
arithmetic differs from ``step_plain``; α always uses the exactly measured
rᵀr, so the recurrence error never compounds into the iterates.  The
updates land one launch later than in ``step_plain`` (a pending (α, D, V)
pair is flushed in O(n·t) XLA once, after the loop), which is what lets a
single grid sweep both consume D and produce the next state.  Convergence
masking keeps ``step_plain`` semantics exactly: frozen columns get α = 0
(their U/R freeze bitwise; their D keeps evolving harmlessly — every
consumer of D is masked through α/β).

The fused path supports only the identity preconditioner: a
``precond_solve`` cannot run inside the kernel epilogue, so combining the
two raises immediately rather than silently falling back (set
``precond_rank=0``, or drop ``fuse_cg``).  It composes with the f32
residual refresh: refresh steps flush the pending update, measure the
true residual through ``refresh_matmul`` and re-enter the fused loop with
a (α=0, β=1, γ=0) no-op prologue — all the ``step_refresh`` guards
(curvature, momentum keep/restart, best-iterate snapshot, adaptive
period) apply unchanged.
"""

from __future__ import annotations

import functools
import time
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import obs


class MBCGResult(NamedTuple):
    solves: jax.Array  # (..., n, t)  — K̂⁻¹B
    tridiag_alpha: jax.Array  # (..., t, p)   CG step sizes  α_j  (masked: 0 when inactive)
    tridiag_beta: jax.Array  # (..., t, p)   CG momenta     β_j  (β_p unused)
    active_steps: jax.Array  # (..., t, p)   bool: was column still unconverged at step j
    num_iters: jax.Array  # (..., t)     iterations actually used per column
    residual_norm: jax.Array  # (..., t)     final relative residual ‖r‖/‖b‖
    basis: jax.Array | None = None  # (..., n, t, p) preconditioned Lanczos
    # basis W (columns z_j/√(r_jᵀz_j)); populated only with return_basis=True.
    # Satisfies K̂⁻¹ ≈ W T̃⁻¹ Wᵀ per RHS column — the LOVE-style posterior
    # covariance cache (see repro.core.inference.build_posterior_cache).
    num_refreshes: jax.Array | None = None  # scalar int32: in-loop f32
    # residual refreshes actually taken (None when refresh_every == 0) —
    # the FLOP-accounting diagnostic for the adaptive refresh policy.
    num_rescues: jax.Array | None = None  # scalar int32: column-steps where
    # the non-finite rescue pulled the trajectory back to the best snapshot
    # (None when refresh_every == 0).  >0 means the solve path was
    # contaminated at least once — repro.core.health classifies it RESCUED.
    num_curvature_skips: jax.Array | None = None  # scalar int32:
    # column-steps where the curvature guard saw dᵀK̂d ≤ 0 (or non-finite)
    # and zeroed α (None when refresh_every == 0) — the STALLED signal.


# Adaptive refresh: stretch the period only while the recursive residual is
# tracking the true one this tightly (max per-column relative drift).  The
# momentum guard fires at REFRESH_MOMENTUM_GATE; stretching stops well
# before that so the geometric schedule never rides the edge of the
# honesty gate.
REFRESH_DRIFT_GATE = 0.1

# Momentum keep/restart threshold at a refresh: the CG direction is kept
# (β against the refreshed residual) while the recursive residual's
# relative drift from the true one stays below this; past it the direction
# restarts from the (preconditioned) true residual.  Shared by the unfused
# and fused refresh steps — they must apply the same policy.
REFRESH_MOMENTUM_GATE = 0.25

#: CGStepFn — the pluggable fused-iteration seam.  Signature::
#:
#:     step(U, R, D, V, alpha, beta, gamma)
#:         -> (U', R', D', V', (dv, rr, rv, vv))
#:
#: with state of shape (..., n, t), per-column scalars (..., t).  The step
#: must apply the pending updates  U += α∘D, R −= α∘V, D = γ∘R + β∘D  and
#: then compute V' = K̂ @ D' plus the four reductions dᵀV, rᵀr, rᵀV, vᵀV of
#: the UPDATED state.  Operators advertise one via
#: ``LinearOperator.fused_cg_step_fn()``; :func:`xla_cg_step` builds the
#: pure-XLA reference from any matmul (the semantics every fused kernel
#: must match — and the testing oracle for them).
#:
#: The contract says nothing about HOW the step covers the row range, which
#: is what lets the partitioned operators plug in a PANEL-fused step — one
#: kernel launch per streamed row-panel (sharded: per device band), with
#: the four reductions accumulated across the panel loop and returned once
#: — without this loop changing at all: `_fused_loop` only ever sees whole
#: iterations and whole (…, t) reductions.
CGStepFn = Callable


def xla_cg_step(matmul: Callable[[jax.Array], jax.Array]) -> CGStepFn:
    """Reference :data:`CGStepFn` from a plain blackbox matmul.

    Pure XLA — no launch/HBM savings, but bit-for-bit the state recurrence
    the fused Pallas kernel implements, so tests (and operators without a
    fused kernel that still want the pipelined recurrence) can run the
    fused mBCG loop anywhere."""

    def step(U, R, D, V, alpha, beta, gamma):
        a = alpha[..., None, :]
        U = U + a * D
        R = R - a * V
        D = gamma[..., None, :] * R + beta[..., None, :] * D
        V = matmul(D).astype(R.dtype)
        dv = jnp.sum(D * V, axis=-2)
        rr = jnp.sum(R * R, axis=-2)
        rv = jnp.sum(R * V, axis=-2)
        vv = jnp.sum(V * V, axis=-2)
        return U, R, D, V, (dv, rr, rv, vv)

    return step


def _fused_loop(
    fused_step: CGStepFn,
    Bc: jax.Array,
    b_norm: jax.Array,
    *,
    tol: float,
    max_iters: int,
    return_basis: bool,
    refresh_every: int,
    refresh_matmul,
    refresh_adaptive: bool,
    refresh_max_period: int,
):
    """The fused-launch mBCG loop: ONE CGStepFn call per iteration, O(t)
    scalar arithmetic in XLA between launches.

    State convention: the (α, β, γ) computed after launch k are *pending* —
    launch k+1's prologue applies them before its matmul, so U/R in the
    carry always trail the scalars by one rank-1 update.  The pending pair
    is flushed once, after the loop.  α uses the exactly measured rᵀr each
    launch; only β rides the pipelined recurrence rz' = rz − 2α·rᵀV + α²·vᵀV
    (the next launch re-measures rᵀr, so the recurrence never compounds).

    Returns ``(U_final, per_step_outs, res_final, num_refreshes)`` with the
    same per-step output convention as the unfused scan bodies."""
    compute_dtype = Bc.dtype
    t = Bc.shape[-1]
    zt = jnp.zeros(Bc.shape[:-2] + (t,), compute_dtype)
    ones_t = jnp.ones_like(zt)
    U0 = jnp.zeros_like(Bc)
    V0 = jnp.zeros_like(Bc)
    # D0 = 0 is arbitrary: the first launch runs with (α=0, β=0, γ=1), whose
    # prologue produces U=0, R=B, D=R — the textbook CG start.
    core0 = (U0, Bc, jnp.zeros_like(Bc), V0, zt, zt, ones_t)

    def fused_plain(carry, it):
        U, R, D, V, alpha, beta, gamma, active = carry
        U, R, D, V, (dv, rr, rv, vv) = fused_step(U, R, D, V, alpha, beta, gamma)
        rz = jnp.maximum(rr, 0.0)  # identity precond: rᵀz = ‖r‖², measured
        res = jnp.sqrt(rz) / b_norm
        active = active & (res > tol)
        alpha = jnp.where(active, _safe_div(rz, dv), 0.0)
        rz_next = jnp.maximum(rz - 2.0 * alpha * rv + alpha * alpha * vv, 0.0)
        beta = jnp.where(active, _safe_div(rz_next, rz), 0.0)
        gamma = jnp.ones_like(beta)
        out = (alpha, beta, active)
        if return_basis:
            # preconditioned Lanczos vector (identity precond: z_j = r_j)
            out = out + (
                jnp.where(active[..., None, :], R * _safe_rsqrt(rz)[..., None, :], 0.0),
            )
        return (U, R, D, V, alpha, beta, gamma, active), out

    def fused_refresh(carry, it):
        (U, R, D, V, alpha, beta, gamma,
         U_best, R_best, best_res, period, since, nref, ncurv, nresc) = carry
        U, Rk, D, V, (dv, rr, rv, vv) = fused_step(U, R, D, V, alpha, beta, gamma)
        rz = jnp.maximum(rr, 0.0)
        res = jnp.sqrt(rz) / b_norm
        # masking re-derived from the measured ‖r‖ every launch (columns may
        # REactivate after a refresh exposed a lying recursive residual)
        active = jnp.minimum(res, best_res) > tol
        # curvature guard: reduced-precision noise can round dᵀK̂d ≤ 0.
        # ~(dv > 0) rather than (dv <= 0): a NaN dv fails both comparisons
        # and must count as a guard trip, not slip through uncounted.
        ncurv = ncurv + jnp.sum(active & ~(dv > 0)).astype(jnp.int32)
        alpha = jnp.where((dv > 0) & active, _safe_div(rz, dv), 0.0)
        do_refresh = since + 1 >= period

        def _advance(U, Rk, D, V):
            rz_next = jnp.maximum(rz - 2.0 * alpha * rv + alpha * alpha * vv, 0.0)
            beta_n = jnp.where(active, _safe_div(rz_next, rz), 0.0)
            return (U, Rk, D, alpha, beta_n, jnp.ones_like(beta_n), beta_n,
                    U_best, R_best, best_res, jnp.float32(0.0), jnp.int32(0))

        def _refresh(U, Rk, D, V):
            # flush the pending update in f32 XLA (refresh steps only), then
            # the same guards as step_refresh: NaN hygiene, best-iterate
            # snapshot, non-finite rescue, drift-gated momentum keep/restart.
            # The α ≠ 0 guards matter under transient non-finite faults:
            # a poisoned D/V must not leak NaN into a frozen column through
            # 0·NaN (which is NaN, not 0).
            a = alpha[..., None, :]
            Uf = jnp.where(a != 0, U + a * D, U)
            Rrec = jnp.where(a != 0, Rk - a * V, Rk)
            Rf = Bc - refresh_matmul(Uf).astype(compute_dtype)
            res_f = jnp.linalg.norm(Rf, axis=-2) / b_norm
            res_f = jnp.where(jnp.isfinite(res_f), res_f, jnp.inf)
            better = res_f < best_res
            Ub = jnp.where(better[..., None, :], Uf, U_best)
            Rb = jnp.where(better[..., None, :], Rf, R_best)
            rb = jnp.minimum(res_f, best_res)
            pull = jnp.isinf(res_f)
            Uc = jnp.where(pull[..., None, :], Ub, Uf)
            Rf = jnp.where(pull[..., None, :], Rb, Rf)
            rzf = jnp.sum(Rf * Rf, axis=-2)
            drift = jnp.linalg.norm(Rrec - Rf, axis=-2) / jnp.maximum(
                jnp.linalg.norm(Rf, axis=-2), 1e-30
            )
            beta_f = jnp.where(drift < REFRESH_MOMENTUM_GATE, _safe_div(rzf, rz), 0.0)
            bD = beta_f[..., None, :]
            # β = 0 is a direction RESTART: take Rf itself, never 0·D — a
            # non-finite D would otherwise poison the restarted direction
            Df = jnp.where(bD > 0, Rf + bD * D, Rf)  # Zf = Rf (identity precond)
            zero = jnp.zeros_like(alpha)
            # the state is now fully updated: the next launch must run a
            # no-op prologue, encoded as (α=0, β=1, γ=0) → D_new = D
            return (Uc, Rf, Df, zero, jnp.ones_like(zero), zero, beta_f,
                    Ub, Rb, rb, jnp.max(drift), jnp.sum(pull).astype(jnp.int32))

        (U, Rn, Dn, alpha_n, beta_n, gamma_n, beta_emit,
         U_best, R_best, best_res, drift_max, resc_inc) = jax.lax.cond(
            do_refresh, _refresh, _advance, U, Rk, D, V
        )
        since = jnp.where(do_refresh, 0, since + 1)
        nref = nref + do_refresh.astype(jnp.int32)
        nresc = nresc + resc_inc
        if refresh_adaptive:
            cap = refresh_max_period if refresh_max_period > 0 else max_iters
            stretched = jnp.minimum(period * 2, cap)
            updated = jnp.where(
                drift_max < REFRESH_DRIFT_GATE, stretched, refresh_every
            )
            period = jnp.where(do_refresh, updated, period)
        out = (alpha, beta_emit, active)
        if return_basis:
            out = out + (
                jnp.where(active[..., None, :], Rk * _safe_rsqrt(rz)[..., None, :], 0.0),
            )
        return (U, Rn, Dn, V, alpha_n, beta_n, gamma_n,
                U_best, R_best, best_res, period, since, nref, ncurv, nresc), out

    if refresh_every:
        res0 = jnp.linalg.norm(Bc, axis=-2) / b_norm
        carry0 = core0 + (U0, Bc, res0,
                          jnp.int32(refresh_every), jnp.int32(0), jnp.int32(0),
                          jnp.int32(0), jnp.int32(0))
        final, outs = jax.lax.scan(fused_refresh, carry0, jnp.arange(max_iters))
        U, _, D, V, alpha_c = final[0], final[1], final[2], final[3], final[4]
        # flush the pending update (no-op when the last step refreshed), then
        # one last f32 refresh so post-final-cycle progress counts
        a = alpha_c[..., None, :]
        U = jnp.where(a != 0, U + a * D, U)
        U_best, best_res = final[7], final[9]
        res_t = jnp.linalg.norm(
            Bc - refresh_matmul(U).astype(compute_dtype), axis=-2
        ) / b_norm
        res_t = jnp.where(jnp.isfinite(res_t), res_t, jnp.inf)
        U = jnp.where((res_t < best_res)[..., None, :], U, U_best)
        return (U, outs, jnp.minimum(res_t, best_res),
                final[12], final[14], final[13])

    active0 = jnp.ones_like(zt, dtype=bool)
    carry0 = core0 + (active0,)
    final, outs = jax.lax.scan(fused_plain, carry0, jnp.arange(max_iters))
    U, R, D, V, alpha_c = final[0], final[1], final[2], final[3], final[4]
    a = alpha_c[..., None, :]
    U = U + a * D
    R = R - a * V
    res_final = jnp.linalg.norm(R, axis=-2) / b_norm
    return U, outs, res_final, None, None, None


def _safe_div(num, den):
    ok = jnp.abs(den) > 1e-30
    return jnp.where(ok, num / jnp.where(ok, den, 1.0), 0.0)


def _safe_rsqrt(x):
    ok = x > 1e-30
    return jnp.where(ok, jax.lax.rsqrt(jnp.where(ok, x, 1.0)), 0.0)


def _named_scope(name: str):
    """Decorator: trace each call of the function under a fresh
    ``jax.named_scope(name)``, so every op it stages carries ``name`` in its
    ``op_name`` metadata (a scope object holds its caller's name stack, so
    one instance cannot be shared between calls)."""

    def wrap(fn):
        @functools.wraps(fn)
        def scoped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return scoped

    return wrap


@partial(
    jax.jit,
    static_argnames=(
        "matmul",
        "precond_solve",
        "max_iters",
        "return_basis",
        "refresh_every",
        "refresh_matmul",
        "refresh_adaptive",
        "refresh_max_period",
        "fused_step",
    ),
)
@_named_scope("bbmm.mbcg")
def _mbcg_jit(
    matmul: Callable[[jax.Array], jax.Array],
    B: jax.Array,
    *,
    precond_solve: Callable[[jax.Array], jax.Array] | None = None,
    max_iters: int = 20,
    tol: float = 1e-4,
    return_basis: bool = False,
    refresh_every: int = 0,
    refresh_matmul: Callable[[jax.Array], jax.Array] | None = None,
    refresh_adaptive: bool = False,
    refresh_max_period: int = 0,
    fused_step: CGStepFn | None = None,
) -> MBCGResult:
    """Solve K̂⁻¹B for all columns (and all leading batch dims) of B at once.

    This is the jitted body; :func:`mbcg` is the public entry point (same
    signature) whose only addition is host-side telemetry.  Every op of the
    solve, all four loop bodies included, is staged under the
    ``bbmm.mbcg`` name scope, which a device trace reads its ops by.

    Args:
      matmul: blackbox ``M ↦ K̂ @ M`` for (..., n, t) M (must broadcast over
        any leading batch dims B carries).
      B: (n,), (n, t) or (..., n, t) right-hand sides (first column is
        typically y, the rest are probe vectors z_i).
      precond_solve: ``R ↦ P̂⁻¹ R``; identity if None.
      max_iters: fixed trip count p.
      tol: relative-residual convergence threshold per column.
      return_basis: also record the preconditioned Lanczos basis
        W = [z_j/√(r_jᵀz_j)] per column — O(p·n·t) extra memory, used by the
        posterior solve cache.
      refresh_every: if > 0, every ``refresh_every`` steps recompute the
        TRUE residual r = b − K̂u through ``refresh_matmul`` in full
        precision and re-derive the per-column convergence masks from it
        (reactivating columns whose recursive residual had drifted below
        their true one), with the curvature / momentum / best-snapshot
        guards described in the module docstring — the residual-replacement
        scheme that keeps ``tol`` honest when ``matmul`` runs at reduced
        precision.  Costs one f32 matmul per period plus two (n, t)
        snapshot buffers.
      refresh_matmul: the full-precision ``M ↦ K̂ @ M`` used by the refresh
        (defaults to ``matmul`` — useful only as drift control then).
      refresh_adaptive: stretch the refresh period geometrically (×2 per
        refresh, capped at ``refresh_max_period``) while the measured
        recursive-vs-true drift stays below ``REFRESH_DRIFT_GATE``; snap
        back to ``refresh_every`` on a violation.  Recovers the f32-matmul
        FLOPs the static schedule burns on well-conditioned solves.
      refresh_max_period: cap for the adaptive stretch (0 → ``max_iters``,
        i.e. effectively uncapped).
      fused_step: a :data:`CGStepFn` executing one whole CG iteration as a
        single fused launch (state updates + K̂·D + the four per-column
        reductions) — see the module docstring.  Only the identity
        preconditioner composes with it; passing ``precond_solve`` too is
        an error, never a silent fallback.  Obtained from
        ``LinearOperator.fused_cg_step_fn()`` or :func:`xla_cg_step`.
    """
    if fused_step is not None and precond_solve is not None:
        raise ValueError(
            "mbcg: fused_step cannot run a precond_solve inside the fused "
            "kernel iteration — the fused CG path supports only the identity "
            "preconditioner.  Set precond_rank=0 (BBMMSettings) to drop the "
            "pivoted-Cholesky preconditioner, or disable fuse_cg to keep it."
        )
    if precond_solve is None:
        precond_solve = lambda R: R
    if refresh_matmul is None:
        refresh_matmul = matmul

    B = jnp.asarray(B)
    squeeze = B.ndim == 1
    if squeeze:
        B = B[:, None]
    n, t = B.shape[-2:]
    compute_dtype = jnp.promote_types(B.dtype, jnp.float32)
    Bc = B.astype(compute_dtype)

    b_norm = jnp.linalg.norm(Bc, axis=-2)  # (..., t)
    b_norm = jnp.where(b_norm == 0, 1.0, b_norm)

    if fused_step is not None:
        U, outs, res_final, num_refreshes, num_rescues, num_curvature_skips = _fused_loop(
            fused_step,
            Bc,
            b_norm,
            tol=tol,
            max_iters=max_iters,
            return_basis=return_basis,
            refresh_every=refresh_every,
            refresh_matmul=refresh_matmul,
            refresh_adaptive=refresh_adaptive,
            refresh_max_period=refresh_max_period,
        )
        alphas, betas, actives = outs[:3]
        num_iters = jnp.sum(actives, axis=0)
        solves = U.astype(B.dtype)
        basis = None
        if return_basis:
            basis = jnp.moveaxis(outs[3], 0, -1)  # (..., n, t, p)
        if squeeze:
            solves = solves[..., 0]
            if basis is not None:
                basis = basis[..., 0, :]
        return MBCGResult(
            solves=solves,
            tridiag_alpha=jnp.moveaxis(alphas, 0, -1),
            tridiag_beta=jnp.moveaxis(betas, 0, -1),
            active_steps=jnp.moveaxis(actives, 0, -1),
            num_iters=num_iters,
            residual_norm=res_final,
            basis=basis,
            num_refreshes=num_refreshes,
            num_rescues=num_rescues,
            num_curvature_skips=num_curvature_skips,
        )

    U0 = jnp.zeros_like(Bc)
    R0 = Bc  # r = b - K u, u0 = 0
    Z0 = precond_solve(R0).astype(compute_dtype)
    D0 = Z0
    rz0 = jnp.sum(R0 * Z0, axis=-2)  # (..., t)
    active0 = jnp.linalg.norm(R0, axis=-2) / b_norm > tol

    def step_plain(carry, it):
        U, R, Z, D, rz, active = carry
        V = matmul(D).astype(compute_dtype)
        dv = jnp.sum(D * V, axis=-2)
        alpha = _safe_div(rz, dv)
        alpha = jnp.where(active, alpha, 0.0)  # converged columns freeze

        U = U + alpha[..., None, :] * D
        R = R - alpha[..., None, :] * V
        Znew = precond_solve(R).astype(compute_dtype)
        rz_new = jnp.sum(R * Znew, axis=-2)
        beta = _safe_div(rz_new, rz)
        beta = jnp.where(active, beta, 0.0)
        D = jnp.where(active[..., None, :], Znew + beta[..., None, :] * D, D)

        res = jnp.linalg.norm(R, axis=-2) / b_norm
        next_active = active & (res > tol)
        out = (alpha, beta, active)
        if return_basis:
            # preconditioned Lanczos vector of this step: z_j/√(r_jᵀz_j),
            # zeroed once the column has converged (identity-padded T̃ block)
            out = out + (jnp.where(active[..., None, :], Z * _safe_rsqrt(rz)[..., None, :], 0.0),)
        return (U, R, Znew, D, jnp.where(active, rz_new, rz), next_active), out

    def step_refresh(carry, it):
        (U, R, Z, D, rz, active, U_best, R_best, best_res,
         period, since, nref, ncurv, nresc) = carry
        V = matmul(D).astype(compute_dtype)
        dv = jnp.sum(D * V, axis=-2)
        alpha = _safe_div(rz, dv)
        # curvature guard: reduced-precision noise can round dᵀK̂d ≤ 0 —
        # skip the (garbage) step; the direction restarts at the refresh.
        # Counted via ~(dv > 0), not (dv <= 0): NaN dv fails both
        # comparisons and must register as a guard trip.
        ncurv = ncurv + jnp.sum(active & ~(dv > 0)).astype(jnp.int32)
        alpha = jnp.where(dv > 0, alpha, 0.0)
        alpha = jnp.where(active, alpha, 0.0)
        # α ≠ 0 guards: a transiently non-finite D/V must not leak NaN into
        # a frozen or curvature-skipped column through 0·NaN
        a = alpha[..., None, :]
        U = jnp.where(a != 0, U + a * D, U)
        Rrec = jnp.where(a != 0, R - a * V, R)
        do_refresh = since + 1 >= period

        def _advance(U, Rrec, D):
            Znew = precond_solve(Rrec).astype(compute_dtype)
            rz_new = jnp.sum(Rrec * Znew, axis=-2)
            beta = jnp.where(active, _safe_div(rz_new, rz), 0.0)
            Dn = jnp.where(active[..., None, :], Znew + beta[..., None, :] * D, D)
            return (U, Rrec, Znew, Dn, jnp.where(active, rz_new, rz),
                    U_best, R_best, best_res, beta, jnp.float32(0.0),
                    jnp.int32(0))

        # f32 residual refresh: replace the recursive residual with the true
        # b − K̂u, re-derive the masks from it (columns may REactivate), and
        # apply the momentum / best-solution / rescue guards per column.
        def _refresh(U, Rrec, D):
            Rf = Bc - refresh_matmul(U).astype(compute_dtype)
            res_f = jnp.linalg.norm(Rf, axis=-2) / b_norm
            # NaN hygiene FIRST: an overflowed trajectory must read as ∞,
            # not poison the best-so-far bookkeeping through jnp.minimum
            res_f = jnp.where(jnp.isfinite(res_f), res_f, jnp.inf)
            # best-solution snapshot: the returned solve is the best refreshed
            # iterate per column, so the reported answer is monotone even if
            # the bf16 trajectory wanders between refreshes
            better = res_f < best_res
            Ub = jnp.where(better[..., None, :], U, U_best)
            Rb = jnp.where(better[..., None, :], Rf, R_best)
            rb = jnp.minimum(res_f, best_res)
            # rescue: only a NON-FINITE trajectory restarts from the best
            # iterate (a merely-larger residual is left alone — CG residuals
            # are legitimately non-monotone mid-transient, and pulling back
            # on any regression deterministically livelocks the column)
            pull = jnp.isinf(res_f)
            Uc = jnp.where(pull[..., None, :], Ub, U)
            Rf = jnp.where(pull[..., None, :], Rb, Rf)
            res_f = jnp.where(pull, rb, res_f)
            Zf = precond_solve(Rf).astype(compute_dtype)
            rzf = jnp.sum(Rf * Zf, axis=-2)
            # momentum: keep the CG direction where the recursive residual is
            # still telling the truth (small relative drift from the true
            # one — the quantity the refresh exists to correct); restart it
            # from the preconditioned true residual where the recursion has
            # drifted.  Progress-based criteria are wrong here: CG residuals
            # are legitimately non-monotone mid-transient, and restarting on
            # every non-contracting cycle destroys superlinear convergence.
            drift = jnp.linalg.norm(Rrec - Rf, axis=-2) / jnp.maximum(
                jnp.linalg.norm(Rf, axis=-2), 1e-30
            )
            beta_f = jnp.where(drift < REFRESH_MOMENTUM_GATE, _safe_div(rzf, rz), 0.0)
            bD = beta_f[..., None, :]
            # β = 0 is a direction RESTART: take Zf itself, never 0·D — a
            # non-finite D would otherwise poison the restarted direction
            Df = jnp.where(bD > 0, Zf + bD * D, Zf)
            return (Uc, Rf, Zf, Df, rzf, Ub, Rb, rb, beta_f, jnp.max(drift),
                    jnp.sum(pull).astype(jnp.int32))

        (U, Rn, Zn, Dn, rz_c, U_best, R_best, best_res, beta, drift_max,
         resc_inc) = (
            jax.lax.cond(do_refresh, _refresh, _advance, U, Rrec, D)
        )
        since = jnp.where(do_refresh, 0, since + 1)
        nref = nref + do_refresh.astype(jnp.int32)
        nresc = nresc + resc_inc
        if refresh_adaptive:
            # geometric stretch while the recursion tracks the truth; snap
            # back to the base period the moment the drift gate is violated
            cap = refresh_max_period if refresh_max_period > 0 else max_iters
            stretched = jnp.minimum(period * 2, cap)
            updated = jnp.where(
                drift_max < REFRESH_DRIFT_GATE, stretched, refresh_every
            )
            period = jnp.where(do_refresh, updated, period)
        out = (alpha, beta, active)
        if return_basis:
            out = out + (jnp.where(active[..., None, :], Z * _safe_rsqrt(rz)[..., None, :], 0.0),)
        res = jnp.linalg.norm(Rn, axis=-2) / b_norm
        # a column whose best refreshed iterate already meets tol freezes
        next_active = jnp.minimum(res, best_res) > tol
        return (U, Rn, Zn, Dn, rz_c, next_active, U_best, R_best, best_res,
                period, since, nref, ncurv, nresc), out

    carry0 = (U0, R0, Z0, D0, rz0, active0)
    step = step_plain
    if refresh_every:
        res0 = jnp.linalg.norm(R0, axis=-2) / b_norm
        carry0 = carry0 + (U0, R0, res0,
                           jnp.int32(refresh_every), jnp.int32(0), jnp.int32(0),
                           jnp.int32(0), jnp.int32(0))
        step = step_refresh
    final_carry, outs = jax.lax.scan(step, carry0, jnp.arange(max_iters))
    U, R = final_carry[0], final_carry[1]
    alphas, betas, actives = outs[:3]

    num_refreshes = num_rescues = num_curvature_skips = None
    if refresh_every:
        # one last f32 refresh so post-final-cycle progress counts, then the
        # best refreshed iterate per column is the returned solve — with its
        # TRUE relative residual as residual_norm (never the recursive lie)
        U_best, best_res = final_carry[6], final_carry[8]
        res_t = jnp.linalg.norm(
            Bc - refresh_matmul(U).astype(compute_dtype), axis=-2
        ) / b_norm
        res_t = jnp.where(jnp.isfinite(res_t), res_t, jnp.inf)
        U = jnp.where((res_t < best_res)[..., None, :], U, U_best)
        res_final = jnp.minimum(res_t, best_res)
        num_refreshes = final_carry[11]
        num_curvature_skips = final_carry[12]
        num_rescues = final_carry[13]
    else:
        res_final = jnp.linalg.norm(R, axis=-2) / b_norm
    num_iters = jnp.sum(actives, axis=0)  # (..., t)

    solves = U.astype(B.dtype)
    basis = None
    if return_basis:
        basis = jnp.moveaxis(outs[3], 0, -1)  # (..., n, t, p)
    if squeeze:
        solves = solves[..., 0]
        if basis is not None:
            basis = basis[..., 0, :]
    return MBCGResult(
        solves=solves,
        tridiag_alpha=jnp.moveaxis(alphas, 0, -1),  # (..., t, p)
        tridiag_beta=jnp.moveaxis(betas, 0, -1),
        active_steps=jnp.moveaxis(actives, 0, -1),
        num_iters=num_iters,
        residual_norm=res_final,
        basis=basis,
        num_refreshes=num_refreshes,
        num_rescues=num_rescues,
        num_curvature_skips=num_curvature_skips,
    )


def mbcg(
    matmul: Callable[[jax.Array], jax.Array],
    B: jax.Array,
    *,
    precond_solve: Callable[[jax.Array], jax.Array] | None = None,
    max_iters: int = 20,
    tol: float = 1e-4,
    return_basis: bool = False,
    refresh_every: int = 0,
    refresh_matmul: Callable[[jax.Array], jax.Array] | None = None,
    refresh_adaptive: bool = False,
    refresh_max_period: int = 0,
    fused_step: CGStepFn | None = None,
) -> MBCGResult:
    """Solve K̂⁻¹B — the instrumented public entry over :func:`_mbcg_jit`.

    See :func:`_mbcg_jit` for the full argument reference; this wrapper is
    bit-identical to it and adds only telemetry, under the same
    device-side-scalars-only discipline as ``health.classify_mbcg``:

    * **no sink installed** (the common case): one module-attribute read
      and a ``None`` check, then straight into the jitted body — measured
      as ``obs_overhead_frac`` in ``benchmarks/health.py``;
    * **metrics registry installed** (eager callers only): after the solve,
      the device-side scalar telemetry (iterations, refreshes, rescues,
      curvature skips) is host-read and folded into ``cg_*`` series, plus
      an amortised per-iteration wall time (first call includes compile);
    * **trace() active**: the call is wrapped in an ``"mbcg"`` span;
    * **called under jit/grad** (``B`` is a tracer): straight into the
      jitted body as with no sink, so nothing is recorded at trace time and
      the traced program — and its jaxpr — is unchanged.  Inside a jitted
      step the solve's iterations and time are read from the device trace
      (the ``bbmm.mbcg`` scope), not from here.
    """
    if isinstance(B, jax.core.Tracer) or (
        obs.active() is None and obs.active_trace() is None
    ):
        return _mbcg_jit(
            matmul,
            B,
            precond_solve=precond_solve,
            max_iters=max_iters,
            tol=tol,
            return_basis=return_basis,
            refresh_every=refresh_every,
            refresh_matmul=refresh_matmul,
            refresh_adaptive=refresh_adaptive,
            refresh_max_period=refresh_max_period,
            fused_step=fused_step,
        )
    with obs.span("mbcg", fused=fused_step is not None, refresh=bool(refresh_every)):
        t0 = time.perf_counter()
        result = _mbcg_jit(
            matmul,
            B,
            precond_solve=precond_solve,
            max_iters=max_iters,
            tol=tol,
            return_basis=return_basis,
            refresh_every=refresh_every,
            refresh_matmul=refresh_matmul,
            refresh_adaptive=refresh_adaptive,
            refresh_max_period=refresh_max_period,
            fused_step=fused_step,
        )
        _obs_record_mbcg(result, t0, fused=fused_step is not None)
    return result


def _obs_scalar(x) -> int | None:
    """Worst-column host int from device scalar telemetry; None if tracing."""
    if x is None or isinstance(x, jax.core.Tracer):
        return None
    try:
        return int(jax.device_get(jnp.max(jnp.asarray(x))))
    except (TypeError, jax.errors.TracerArrayConversionError):
        return None


def _obs_record_mbcg(result: MBCGResult, t0: float, *, fused: bool) -> None:
    """Fold one eager mbcg call into the metrics registry (if installed)."""
    if obs.active() is None:
        return
    iters = _obs_scalar(result.num_iters)
    if iters is None:
        return  # under an outer jit/grad trace: leave the jaxpr untouched
    # the device_get above synchronised, so this wall time covers the solve
    wall = time.perf_counter() - t0
    mode = "fused" if fused else "plain"
    obs.inc("cg_solves_total", mode=mode)
    obs.observe("cg_iterations", iters, mode=mode)
    obs.observe("cg_iteration_seconds", wall / max(iters, 1), mode=mode)
    for name, raw in (
        ("cg_refreshes_total", result.num_refreshes),
        ("cg_rescues_total", result.num_rescues),
        ("cg_curvature_skips_total", result.num_curvature_skips),
    ):
        count = _obs_scalar(raw)
        if count:
            obs.inc(name, count)


def tridiag_matrices(result: MBCGResult) -> jax.Array:
    """Assemble the (..., t, p, p) Lanczos tridiagonal matrices T̃_i from the
    CG coefficients (paper Observation 3 / eq. S5):

        T[0,0]   = 1/α₁
        T[j,j]   = 1/α_{j+1} + β_j/α_j
        T[j,j+1] = T[j+1,j] = √β_{j+1}/α_{j+1}

    Steps where a column had already converged are padded as an identity
    block, which leaves e₁ᵀ f(T̃) e₁ unchanged for the leading block.
    Works for any leading batch shape (pure broadcasting — no vmap).
    """
    alphas, betas, active = (
        result.tridiag_alpha,
        result.tridiag_beta,
        result.active_steps,
    )
    p = alphas.shape[-1]

    inv_alpha = _safe_div(jnp.ones_like(alphas), alphas)  # 1/α_j, 0 where masked

    pad = [(0, 0)] * (alphas.ndim - 1) + [(1, 0)]
    # diag_j (0-indexed j): 1/α_j + β_{j-1}/α_{j-1}
    beta_prev = jnp.pad(betas[..., :-1], pad)  # β_{j-1}, 0 for j=0
    alpha_prev_inv = jnp.pad(inv_alpha[..., :-1], pad)
    diag = inv_alpha + beta_prev * alpha_prev_inv
    diag = jnp.where(active, diag, 1.0)  # identity padding

    # offdiag entry (j, j+1) = sqrt(β_j)/α_j using the β produced at step j
    # (Saad: η_{j+1} = sqrt(β_j)/α_j). Valid only if step j+1 is active.
    off = _safe_div(jnp.sqrt(jnp.clip(betas[..., :-1], 0.0)), alphas[..., :-1])
    off = jnp.where(active[..., 1:], off, 0.0)
    off = jnp.pad(off, [(0, 0)] * (off.ndim - 1) + [(0, 1)])  # (..., t, p)

    eye = jnp.eye(p, dtype=diag.dtype)
    upper = off[..., None] * jnp.eye(p, k=1, dtype=diag.dtype)  # [j, j+1] = off_j
    T = diag[..., None] * eye + upper + jnp.swapaxes(upper, -1, -2)
    return T
