"""The BBMM inference engine (paper §4).

A *single* mBCG call yields the three quantities every GP training /
prediction formula needs:

    1. the solve          K̂⁻¹y
    2. the log-det        log|K̂|            (SLQ over recovered tridiags)
    3. the trace term     Tr(K̂⁻¹ dK̂/dθ)    (stochastic trace, Eq. 4)

``inv_quad_logdet`` exposes (yᵀK̂⁻¹y, log|K̂|) as a differentiable JAX
function of *any* LinearOperator pytree.  Its custom VJP implements the
paper's gradient estimators directly:

    ∂(yᵀK̂⁻¹y)/∂θ = −uᵀ (∂K̂/∂θ) u                        with u = K̂⁻¹y
    ∂log|K̂|/∂θ   ≈ (1/t) Σᵢ (P̂⁻¹zᵢ)ᵀ (∂K̂/∂θ) (K̂⁻¹zᵢ)    zᵢ ~ N(0, P̂)

both realized as one ``jax.vjp`` of the blackbox matmul — so any model
expressible as a matmul routine gets exact-in-expectation MLL gradients with
no hand-derived derivative rules (this is the "blackbox" in BBMM, made
stricter than the paper: JAX synthesizes the (∂K̂/∂θ)·M routine too).

Batching: ``y`` may carry leading batch dims (b, n) — e.g. b hyperparameter
restarts or b output heads — provided ``op.matmul`` broadcasts over the same
dims (dense/batched operators do).  The whole engine then runs as ONE fused
mBCG program: per iteration a single (b, n, t) matmul instead of b separate
engine calls.  Probe randomness is shared across the batch, so a batched run
is numerically identical to a Python loop of unbatched runs with one key.

Serving: ``build_posterior_cache`` runs the engine once and packages every
reusable solve (K̂⁻¹y, probe solves, an orthonormal Krylov basis with its
Rayleigh–Ritz Gram factor, the preconditioner factors) into a
:class:`PosteriorCache` pytree.  Repeated posterior queries then cost
O(n·m) — no CG — see the ``gp`` model classes.
"""

from __future__ import annotations

import dataclasses
import math
import time
import warnings
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from . import health
from .health import (
    RungRecord,
    SolveFailure,
    SolveHealthWarning,
    SolveReport,
    classify_mbcg,
)
from .linear_operator import LinearOperator
from .mbcg import mbcg, tridiag_matrices
from .precision import f32_matmuls, precision_compute_dtype, validate_precision
from .preconditioner import IdentityPreconditioner, build_preconditioner
from .slq import logdet_from_mbcg, slq_quadrature


@dataclasses.dataclass(frozen=True)
class BBMMSettings:
    """Inference-engine knobs (paper §6 defaults).

    ``precision="mixed"`` runs the CG-loop kernel matmuls at bf16 with f32
    accumulation (operators opt in via ``with_compute_dtype``) and installs
    the periodic f32 residual refresh (``cg_refresh_every``) inside mBCG so
    the ``cg_tol`` contract survives the reduced-precision matmul noise.
    Preconditioner construction, CG vector arithmetic, gradients and the
    posterior-cache Gram matmul always stay f32.
    """

    num_probes: int = 10  # t — probe vectors for trace/logdet
    max_cg_iters: int = 20  # p — mBCG iterations
    cg_tol: float = 1e-4  # per-column relative residual target
    precond_rank: int = 5  # k — pivoted-Cholesky rank (0 = off)
    precond_jitter: float = 1e-8
    precision: str = "highest"  # "highest" (all f32) | "mixed" (bf16 tiles)
    cg_refresh_every: int = 2  # mixed: f32 residual-refresh period (the
    # tolerance study in benchmarks/speed.py shows period-2 is what keeps
    # 1e-4 tolerances reachable once bf16 RHS rounding noise ~4e-3·κ bites;
    # longer periods trade accuracy floor for fewer f32 matmuls)
    cg_refresh_adaptive: bool = False  # mixed: stretch the refresh period
    # geometrically (×2 per clean refresh, capped below) while the measured
    # recursive-vs-true drift stays under mbcg.REFRESH_DRIFT_GATE, snapping
    # back to cg_refresh_every on violation — recovers the f32-matmul FLOPs
    # the static period-2 default burns on well-conditioned solves
    cg_refresh_max_period: int = 16  # cap for the adaptive stretch
    # (0 → uncapped, i.e. max_cg_iters; positive values are floored at
    # cg_refresh_every)
    fuse_cg: bool = False  # run each mBCG iteration as ONE fused kernel
    # launch when the (prepared) operator advertises a CGStepFn
    # (LinearOperator.fused_cg_step_fn — the Pallas kernel-matmul family
    # does): state updates + K̂·D + the per-column reductions in one grid
    # sweep, leaving only O(t) scalar arithmetic in XLA.  On the
    # partitioned path (mode="pallas_partitioned") the step is PANEL-fused:
    # one launch per streamed row-panel per iteration with the (4, t)
    # reductions carried across the panel loop (sharded: per device band,
    # combined once per iteration) — million-row solves keep the one-launch
    # economy without ever forming an (n × n) working set.  Operators
    # without the capability keep the unfused loop (transparent fallback,
    # warned once per operator), but a non-identity preconditioner cannot
    # fuse: fuse_cg with precond_rank > 0 raises in mbcg rather than
    # silently falling back — set precond_rank=0.  Composes with
    # precision="mixed": the fused launches run bf16 MXU stages, the
    # periodic residual refresh stays an f32 matmul.
    on_failure: str = "warn"  # solve-health policy for the host-level
    # engine entry points (solve / engine_state / build_posterior_cache /
    # extend_posterior_cache) when repro.core.health classifies the mBCG
    # result as unhealthy (anything but CONVERGED):
    #   "raise"   → SolveFailure immediately (fail-stop pipelines)
    #   "warn"    → SolveHealthWarning, return the solve as-is (default —
    #               matches the pre-health behavior, but now observable)
    #   "degrade" → walk the deterministic degradation ladder
    #               (precision_f32 → unfused → extend_budget → small-n
    #               dense_cholesky), returning the first healthy rung with
    #               every attempt recorded in SolveReport.rungs; raise
    #               SolveFailure only when the ladder is exhausted.
    # Inside jit/grad traces classification is a structural no-op (tracers
    # carry no values), so the differentiable MLL path is never perturbed;
    # its health is checked whenever it runs eagerly.
    dense_fallback_max_n: int = 2048  # terminal dense-Cholesky rung of the
    # degradation ladder engages only when the system is at most this large
    # (O(n³)/O(n²) cost — a last resort, not a performance path)
    max_basis_columns: int = 0  # serving-memory budget for the Krylov
    # variance cache under streaming appends (extend_posterior_cache): once
    # the recycled basis would exceed this many columns it is compacted by
    # Rayleigh–Ritz truncation — keep the top-m eigendirections of the
    # small Gram basisᵀK̂basis (still a subspace ⇒ served variances stay
    # conservative; only tightness degrades).  0 = unbounded (the
    # max_staleness rebuild policy is then the only growth bound).
    panel_rows: int = 0  # pallas_partitioned: streamed row-panel height;
    # 0 → the VMEM/HBM-budget auto-chooser
    # (repro.kernels.kernel_matmul.ops.choose_panel_rows) picks the largest
    # aligned panel whose (p × n) slab fits panel_budget_bytes
    panel_budget_bytes: int = 0  # byte budget for one streamed panel slab
    # (0 → ops.PANEL_BUDGET_BYTES, 128 MiB)
    dense_direct_max_n: int = 0  # route exact solves with n ≤ this straight
    # to dense Cholesky BEFORE spinning up mBCG (0 = off).  BENCH shows
    # Cholesky beating the iterative engine below n≈1000 on CPU — tiny
    # systems should not pay probe/preconditioner setup.  The routing is
    # recorded in the solve's health report as a "dense_direct" rung.

    def __post_init__(self):
        if self.on_failure not in ("raise", "degrade", "warn"):
            raise ValueError(
                f"on_failure must be 'raise', 'degrade' or 'warn', got "
                f"{self.on_failure!r}"
            )


def _fused_step_of(op: LinearOperator, settings: BBMMSettings):
    """The operator's CGStepFn when ``fuse_cg`` asks for it and the operator
    advertises one; None otherwise (mbcg then runs the unfused loop)."""
    if not settings.fuse_cg:
        return None
    fn = getattr(op, "fused_cg_step_fn", None)
    return fn() if fn is not None else None


def _solver_matmuls(op: LinearOperator, settings: BBMMSettings):
    """The precision-policy split of one operator into the mBCG matmuls:
    (hot-loop matmul, refresh kwargs, fused CG step or None).  "highest" →
    one f32 matmul, no refresh; "mixed" → a bf16-tile matmul for the loop
    (prepared AFTER the dtype switch so the pre-scaled X is stored
    half-width) plus the f32 matmul of the same operator for the periodic
    residual refresh.  Under ``fuse_cg`` the CGStepFn comes from the SAME
    operator as the hot-loop matmul (so mixed mode fuses bf16 launches
    while the refresh matmul stays f32)."""
    validate_precision(settings.precision)
    solver = op.prepare()
    if settings.precision == "mixed":
        if settings.cg_refresh_every <= 0:
            # the refresh is the mechanism that makes mixed mode honest —
            # running bf16 CG without it silently reports convergence the
            # true residual never reached
            raise ValueError(
                "precision='mixed' requires cg_refresh_every >= 1, got "
                f"{settings.cg_refresh_every}"
            )
        mixed = op.with_compute_dtype(
            precision_compute_dtype(settings.precision)
        ).prepare()
        # cap semantics match mbcg: 0 → uncapped (max_iters); a positive cap
        # is floored at the base period so adaptivity can never shrink it
        cap = settings.cg_refresh_max_period
        if cap > 0:
            cap = max(cap, settings.cg_refresh_every)
        refresh = {
            "refresh_every": settings.cg_refresh_every,
            "refresh_matmul": solver.matmul,
            "refresh_adaptive": settings.cg_refresh_adaptive,
            "refresh_max_period": cap,
        }
        return mixed.matmul, refresh, _fused_step_of(mixed, settings)
    return solver.matmul, {}, _fused_step_of(solver, settings)


def _precond_solve_arg(precond):
    """mbcg's ``precond_solve`` for a built preconditioner: None for the
    identity (mbcg's native no-preconditioner path — and the form the fused
    CG step composes with), the Woodbury solve otherwise."""
    return None if isinstance(precond, IdentityPreconditioner) else precond.solve


# --- degradation ladder ----------------------------------------------------


def _escalation_ladder(settings: BBMMSettings):
    """The deterministic rung sequence for ``on_failure='degrade'``.

    Escalation is CUMULATIVE — each rung keeps every earlier replacement —
    and ordered cheapest-first by what each failure mode usually needs:

      1. ``precision_f32``  — mixed → highest (bf16 stall / drift is the
         most common unhealthy verdict at scale);
      2. ``unfused``        — drop the fused CG kernel (isolates kernel bugs
         from the algorithm; also what re-enables preconditioning);
      3. ``extend_budget``  — double ``max_cg_iters`` and install the
         pivoted-Cholesky preconditioner if it was off (MAX_ITERS on a
         genuinely hard system);
      4. (terminal, built by the caller) small-n dense Cholesky.

    Rungs that do not change anything (already f32, already unfused) are
    skipped, so each returned rung is a genuinely new configuration.
    """
    rungs = []
    s = settings
    if s.precision != "highest":
        s = dataclasses.replace(s, precision="highest")
        rungs.append(("precision_f32", s))
    if s.fuse_cg:
        s = dataclasses.replace(s, fuse_cg=False)
        rungs.append(("unfused", s))
    s = dataclasses.replace(
        s,
        max_cg_iters=2 * s.max_cg_iters,
        precond_rank=s.precond_rank if s.precond_rank > 0 else 5,
        fuse_cg=False,  # a non-identity preconditioner cannot fuse
    )
    rungs.append(("extend_budget", s))
    return rungs


def _apply_policy(report, settings: BBMMSettings, context: str):
    """Check-only health enforcement (no ladder): record + warn/raise.

    Used where a retry is impossible or belongs to the caller — the
    differentiable MLL path (``inv_quad_logdet``; retries there would
    desynchronize the custom-VJP residuals, and training owns its own
    recovery policy in ``fit_gp``).  Tracer-safe: ``report`` is None inside
    jit/grad and the whole call is a no-op.
    """
    if report is None:
        return None
    report = dataclasses.replace(report, context=context)
    health.record(report)
    if not report.healthy and settings.on_failure == "raise":
        raise SolveFailure(report.describe(), report)
    if not report.healthy:
        warnings.warn(
            f"unhealthy solve served as-is ({report.describe()}); set "
            "BBMMSettings(on_failure='degrade') for automatic recovery",
            SolveHealthWarning,
            stacklevel=3,
        )
    return report


def _stamp_last_rung(report, duration_s: float):
    """Attach wall time to the most recent rung attempt of a report."""
    if report is None or not report.rungs:
        return report
    rungs = list(report.rungs)
    rungs[-1] = dataclasses.replace(rungs[-1], duration_s=duration_s)
    return dataclasses.replace(report, rungs=tuple(rungs))


def _run_with_ladder(run, settings: BBMMSettings, *, context, n, dense_fn=None):
    """Execute ``run(settings) -> (value, report|None)`` under the
    ``on_failure`` policy, walking the degradation ladder when asked.

    Every rung attempt — healed, still-unhealthy, or errored (e.g. a
    preconditioner the operator cannot build) — lands in
    ``SolveReport.rungs``, stamped with its wall time, so degradation is
    observable, never silent.  ``dense_fn() -> (value, RungRecord)`` is the
    terminal rung, engaged only for ``n <= settings.dense_fallback_max_n``.
    When a trace is active the whole walk is a ``"solve"`` span with one
    ``"rung:<name>"`` child per attempt.

    ``dense_direct_max_n`` short-circuits the whole machinery for tiny
    systems: below the threshold the dense Cholesky IS the fast path (BENCH
    shows it beating mBCG under n≈1000 on CPU), so it runs FIRST — recorded
    as a "dense_direct" rung in the health report — and the iterative
    engine is only consulted if the direct solve comes back unhealthy.
    """
    with obs.span("solve", context=context, n=n):
        return _ladder_walk(
            run, settings, context=context, n=n, dense_fn=dense_fn
        )


def _ladder_walk(run, settings: BBMMSettings, *, context, n, dense_fn=None):
    if (
        dense_fn is not None
        and 0 < n <= settings.dense_direct_max_n
    ):
        t_dd = time.perf_counter()
        with obs.span("rung:dense_direct", context=context):
            value, rec = dense_fn()
        rec = dataclasses.replace(
            rec, rung="dense_direct", duration_s=time.perf_counter() - t_dd
        )
        if rec.status == health.CONVERGED:
            report = SolveReport(
                status=health.CONVERGED,
                residual_norm=rec.residual_norm or 0.0,
                tol=settings.cg_tol,
                num_iters=0,
                max_iters=settings.max_cg_iters,
                context=context,
                rungs=(rec,),
            )
            health.record(report)
            return value
        # unhealthy direct solve → fall through to the iterative path
        warnings.warn(
            f"dense_direct routing (n={n} <= {settings.dense_direct_max_n}) "
            f"produced an unhealthy solve; running the iterative engine",
            SolveHealthWarning,
            stacklevel=3,
        )
    t_init = time.perf_counter()
    with obs.span("rung:initial", context=context):
        value, report = run(settings)
    if report is None:
        return value  # tracing: health is checked when the caller is eager
    report = _stamp_last_rung(
        dataclasses.replace(report, context=context), time.perf_counter() - t_init
    )
    if report.healthy or settings.on_failure != "degrade":
        _apply_policy(report, settings, context)
        return value

    rungs = list(report.rungs)
    for name, s in _escalation_ladder(settings):
        t_rung = time.perf_counter()
        try:
            with obs.span(f"rung:{name}", context=context):
                value2, rep2 = run(s)
        except Exception as e:  # rung structurally unavailable → next rung
            rungs.append(
                RungRecord(
                    rung=name,
                    status=None,
                    error=repr(e),
                    duration_s=time.perf_counter() - t_rung,
                )
            )
            continue
        dur_rung = time.perf_counter() - t_rung
        if rep2 is None:  # defensive: a traced rerun cannot be classified
            rungs.append(
                RungRecord(
                    rung=name, status=None, error="untraced", duration_s=dur_rung
                )
            )
            continue
        rungs.append(
            RungRecord(
                rung=name,
                status=rep2.status,
                residual_norm=rep2.residual_norm,
                num_iters=rep2.num_iters,
                duration_s=dur_rung,
            )
        )
        if rep2.healthy:
            final = dataclasses.replace(
                rep2, context=context, rungs=tuple(rungs)
            )
            health.record(final)
            warnings.warn(
                f"solve degraded but healed: {final.describe()}",
                SolveHealthWarning,
                stacklevel=3,
            )
            return value2
        report = dataclasses.replace(rep2, context=context)

    if dense_fn is not None and n <= settings.dense_fallback_max_n:
        t_dense = time.perf_counter()
        try:
            with obs.span("rung:dense_cholesky", context=context):
                value3, rec = dense_fn()
        except Exception as e:
            rungs.append(
                RungRecord(
                    rung="dense_cholesky",
                    status=None,
                    error=repr(e),
                    duration_s=time.perf_counter() - t_dense,
                )
            )
        else:
            rec = dataclasses.replace(
                rec, duration_s=time.perf_counter() - t_dense
            )
            rungs.append(rec)
            if rec.status == health.CONVERGED:
                final = dataclasses.replace(
                    report,
                    status=health.CONVERGED,
                    residual_norm=rec.residual_norm
                    if rec.residual_norm is not None
                    else 0.0,
                    num_iters=0,
                    context=context,
                    rungs=tuple(rungs),
                )
                health.record(final)
                warnings.warn(
                    f"solve degraded to dense Cholesky: {final.describe()}",
                    SolveHealthWarning,
                    stacklevel=3,
                )
                return value3

    final = dataclasses.replace(report, rungs=tuple(rungs))
    health.record(final)
    raise SolveFailure(f"degradation ladder exhausted: {final.describe()}", final)


def _dense_chol(op: LinearOperator, n: int):
    """Materialize + factor the operator for the terminal ladder rung.

    Raises SolveFailure when the factorization itself is unhealthy (a
    genuinely non-PSD system has no healthy answer on any rung)."""
    Kd = op.prepare().to_dense().astype(jnp.float32)
    L = jnp.linalg.cholesky(Kd)
    if not bool(jax.device_get(jnp.all(jnp.isfinite(L)))):
        raise SolveFailure(
            f"dense Cholesky fallback failed: operator (n={n}) is not "
            "positive definite"
        )
    return Kd, L


def _dense_rung_record(Kd, rhs, X):
    res = float(
        jax.device_get(
            jnp.max(
                jnp.linalg.norm(rhs - Kd @ X, axis=-2)
                / jnp.maximum(jnp.linalg.norm(rhs, axis=-2), 1e-30)
            )
        )
    )
    status = health.CONVERGED if math.isfinite(res) else health.NON_FINITE
    return RungRecord(
        rung="dense_cholesky", status=status, residual_norm=res, num_iters=0
    )


class InferenceState(NamedTuple):
    """Every quantity a downstream consumer might want from one engine call.

    Leading batch dims (if any) mirror those of ``y``.
    """

    solve_y: jax.Array  # (..., n)  K̂⁻¹y
    inv_quad: jax.Array  # (...,) yᵀK̂⁻¹y
    logdet: jax.Array  # (...,) log|K̂| estimate
    probe_solves: jax.Array  # (..., n, t) K̂⁻¹zᵢ
    probes: jax.Array  # (..., n, t) zᵢ
    precond_probes: jax.Array  # (..., n, t) P̂⁻¹zᵢ
    cg_iters: jax.Array  # (..., t+1) iterations per RHS
    residual: jax.Array  # (..., t+1) final relative residuals


class PosteriorCache(NamedTuple):
    """Reusable posterior-solve state for cheap repeated predictions.

    Built once by :func:`build_posterior_cache` (one engine call + one extra
    blackbox matmul), consumed by the ``predict_cached`` paths of
    ``repro.gp`` models:

      * mean queries reuse ``alpha`` — O(n·s), bitwise identical to the
        uncached path, zero CG iterations;
      * variance queries use the Rayleigh–Ritz pair (``basis``, ``gram_chol``):
        k*ᵀK̂⁻¹k* ≈ vᵀG⁻¹v with v = basisᵀk*, G = basisᵀK̂basis — O(n·m)
        per query and *provably conservative* (the Galerkin projection never
        exceeds the true inverse quadratic form, so the cached posterior
        variance never undershoots the exact one).
    """

    alpha: jax.Array  # (n,)  K̂⁻¹y
    basis: jax.Array | None  # (n, m) orthonormal Krylov cache columns
    gram_chol: jax.Array | None  # (m, m) chol(basisᵀ K̂ basis)
    # basis/gram_chol are None when built with variance_cache=False
    probes: jax.Array  # (n, t)  zᵢ
    probe_solves: jax.Array  # (n, t) K̂⁻¹zᵢ
    precond: Any  # preconditioner factors (reused by uncached predict solves)
    inv_quad: jax.Array  # yᵀK̂⁻¹y (diagnostic / MLL reuse)
    logdet: jax.Array  # log|K̂| estimate (diagnostic / MLL reuse)
    cg_iters: jax.Array  # (t+1,) iterations the build used per RHS


def _run_engine(
    op: LinearOperator,
    y: jax.Array,
    key,
    settings: BBMMSettings,
    *,
    return_basis: bool = False,
    with_logdet: bool = True,
):
    """The shared engine forward pass: preconditioner + probes + ONE mBCG
    over [y | Z], probe tridiag slicing and (optionally) the SLQ log-det.

    Returns (precond, Z, res, probe_solves, logdet) with leading batch dims
    mirroring y's."""
    n = y.shape[-1]
    batch_shape = y.shape[:-1]
    with jax.named_scope("bbmm.precond"):
        precond = build_preconditioner(
            op, settings.precond_rank, jitter=settings.precond_jitter
        )
        Z = precond.sample_probes(key, settings.num_probes, n).astype(y.dtype)
        Z = jnp.broadcast_to(Z, (*batch_shape, n, settings.num_probes))
    B = jnp.concatenate([y[..., None], Z], axis=-1)

    matmul, refresh_kwargs, fused_step = _solver_matmuls(op, settings)
    res = mbcg(
        matmul,
        B,
        precond_solve=_precond_solve_arg(precond),
        max_iters=settings.max_cg_iters,
        tol=settings.cg_tol,
        return_basis=return_basis,
        fused_step=fused_step,
        **refresh_kwargs,
    )
    probe_solves = res.solves[..., 1:]

    if with_logdet:
        with jax.named_scope("bbmm.logdet"):
            probe_res = res._replace(
                solves=probe_solves,
                tridiag_alpha=res.tridiag_alpha[..., 1:, :],
                tridiag_beta=res.tridiag_beta[..., 1:, :],
                active_steps=res.active_steps[..., 1:, :],
                num_iters=res.num_iters[..., 1:],
                residual_norm=res.residual_norm[..., 1:],
            )
            probe_quads, precond_logdet = precond.inv_quad(Z), precond.logdet()
        logdet = logdet_from_mbcg(probe_res, probe_quads, precond_logdet)
    else:
        logdet = jnp.float32(jnp.nan)  # not computed in a mean-only build
    return precond, Z, res, probe_solves, logdet


def _engine_forward_report(
    op: LinearOperator, y: jax.Array, key, settings: BBMMSettings
):
    """Engine forward pass + its health verdict (None under tracing)."""
    precond, Z, res, probe_solves, logdet = _run_engine(op, y, key, settings)
    u = res.solves[..., 0]
    with jax.named_scope("bbmm.precond"):
        precond_probes = precond.solve(Z)
    state = InferenceState(
        solve_y=u,
        inv_quad=jnp.sum(y * u, axis=-1),
        logdet=logdet,
        probe_solves=probe_solves,
        probes=Z,
        precond_probes=precond_probes,
        cg_iters=res.num_iters,
        residual=res.residual_norm,
    )
    report = classify_mbcg(
        res, settings.cg_tol, max_iters=settings.max_cg_iters
    )
    return state, report


def _engine_forward(
    op: LinearOperator,
    y: jax.Array,
    key,
    settings: BBMMSettings,
    *,
    context: str = "mll",
):
    t0 = time.perf_counter()
    state, report = _engine_forward_report(op, y, key, settings)
    # check-only here: this is the differentiable-MLL seam, where a retry
    # would desynchronize the custom-VJP residuals — training's recovery
    # policy lives in fit_gp, serving's in the session layer
    report = _stamp_last_rung(report, time.perf_counter() - t0)
    _apply_policy(report, settings, context)
    return state


@f32_matmuls
def inv_quad_logdet(
    op: LinearOperator,
    y: jax.Array,
    key: jax.Array,
    settings: BBMMSettings = BBMMSettings(),
):
    """Differentiable (yᵀK̂⁻¹y, log|K̂|) for any LinearOperator pytree.

    Batched ``y`` of shape (b, n) returns (b,)-shaped values, still
    differentiable — the custom VJP estimators broadcast."""

    @jax.custom_vjp
    def _iql(op, y, key):
        state = _engine_forward(op, y, key, settings)
        return state.inv_quad, state.logdet

    def _fwd(op, y, key):
        state = _engine_forward(op, y, key, settings)
        residuals = (op, state.solve_y, state.probe_solves, state.precond_probes, key)
        return (state.inv_quad, state.logdet), residuals

    @f32_matmuls  # transposed outside inv_quad_logdet's own call
    def _bwd(residuals, cotangents):
        with jax.named_scope("bbmm.backward"):
            op, u, probe_solves, pinv_z, key = residuals
            g_iq, g_ld = cotangents
            t = probe_solves.shape[-1]
            g_iq = jnp.asarray(g_iq)[..., None, None]  # broadcast over (n, t)
            g_ld = jnp.asarray(g_ld)[..., None, None]

            # One vjp through the blackbox matmul covers both estimators.
            rhs = jnp.concatenate([u[..., None], probe_solves], axis=-1)
            rhs = jax.lax.stop_gradient(rhs)
            cot = jnp.concatenate(
                [(-g_iq) * u[..., None], (g_ld / t) * pinv_z], axis=-1
            )
            cot = cot.astype(rhs.dtype)

            _, matmul_vjp = jax.vjp(lambda o: o.matmul(rhs), op)
            (d_op,) = matmul_vjp(cot)

            d_y = 2.0 * g_iq[..., 0] * u
            d_key = np.zeros(key.shape, dtype=jax.dtypes.float0)
            return d_op, d_y, d_key

    _iql.defvjp(_fwd, _bwd)
    return _iql(op, y, key)


@f32_matmuls
def engine_state(
    op: LinearOperator,
    y: jax.Array,
    key: jax.Array,
    settings: BBMMSettings = BBMMSettings(),
) -> InferenceState:
    """Non-differentiable full engine state (prediction paths, diagnostics).

    Health-checked per ``settings.on_failure`` — under ``"degrade"`` an
    unhealthy run walks the escalation ladder down to a small-n dense
    Cholesky before giving up."""
    n = y.shape[-1]

    def run(s):
        return _engine_forward_report(op, y, key, s)

    def dense():
        Kd, L = _dense_chol(op, n)
        t = settings.num_probes
        Z = IdentityPreconditioner().sample_probes(key, t, n).astype(y.dtype)
        Z = jnp.broadcast_to(Z, (*y.shape[:-1], n, t))
        rhs = jnp.concatenate([y[..., None], Z], axis=-1)
        X = jnp.linalg.solve(Kd, rhs)
        u = X[..., 0]
        logdet = 2.0 * jnp.sum(jnp.log(jnp.diagonal(L, axis1=-2, axis2=-1)), axis=-1)
        state = InferenceState(
            solve_y=u,
            inv_quad=jnp.sum(y * u, axis=-1),
            logdet=logdet,
            probe_solves=X[..., 1:],
            probes=Z,
            precond_probes=Z,
            cg_iters=jnp.zeros(y.shape[:-1] + (t + 1,), jnp.int32),
            residual=jnp.linalg.norm(rhs - Kd @ X, axis=-2)
            / jnp.maximum(jnp.linalg.norm(rhs, axis=-2), 1e-30),
        )
        return state, _dense_rung_record(Kd, rhs, X)

    return _run_with_ladder(
        run, settings, context="engine_state", n=n, dense_fn=dense
    )


@f32_matmuls
def build_posterior_cache(
    op: LinearOperator,
    y: jax.Array,
    key: jax.Array,
    settings: BBMMSettings = BBMMSettings(),
    *,
    variance_cache: bool = True,
) -> PosteriorCache:
    """One engine call → a :class:`PosteriorCache` for O(n·m) serving queries.

    The cache basis spans every solve the engine produced (K̂⁻¹y, the probe
    solves K̂⁻¹zᵢ) plus all preconditioned-Lanczos directions recovered from
    the CG run, orthonormalized by one QR.  Its Gram matrix against K̂ costs
    one extra blackbox matmul here — and buys CG-free posterior variance at
    query time.  (Rank-deficient spans are safe: QR completes them with
    harmless orthonormal directions.)

    ``variance_cache=False`` skips the Lanczos-basis recording, the QR /
    extra matmul / Cholesky, and the SLQ log-det, setting
    ``basis``/``gram_chol`` to None and ``logdet`` to NaN — for consumers
    that only need ``alpha`` (e.g. the uncached prediction paths, which
    compute variance by direct solves).  The probe columns stay in the mBCG
    block either way: the solve arithmetic per column is independent of the
    extra basis output, so ``alpha`` is bitwise the same as the full
    build's (guarded by tests/test_posterior_cache.py).
    """
    if y.ndim != 1:
        raise ValueError("posterior cache supports a single problem (y of shape (n,))")
    n = y.shape[0]

    def run(s):
        precond, Z, res, probe_solves, logdet = _run_engine(
            op, y, key, s, return_basis=variance_cache, with_logdet=variance_cache
        )
        alpha = res.solves[:, 0]
        inv_quad = jnp.dot(y, alpha)

        basis = gram_chol = None
        if variance_cache:
            # Krylov cache subspace: all solves + all recovered Lanczos
            # directions.
            span = jnp.concatenate([res.solves, res.basis.reshape(n, -1)], axis=-1)
            basis, _ = jnp.linalg.qr(span.astype(jnp.float32))  # (n, m)
            KQ = op.prepare().matmul(basis)  # ONE extra blackbox matmul
            gram = basis.T @ KQ
            gram = 0.5 * (gram + gram.T)
            m = gram.shape[0]
            jitter = 1e-6 * jnp.trace(gram) / m
            gram_chol = jnp.linalg.cholesky(
                gram + jitter * jnp.eye(m, dtype=gram.dtype)
            )

        cache = PosteriorCache(
            alpha=alpha,
            basis=basis,
            gram_chol=gram_chol,
            probes=Z,
            probe_solves=probe_solves,
            precond=precond,
            inv_quad=inv_quad,
            logdet=logdet,
            cg_iters=res.num_iters,
        )
        report = classify_mbcg(res, s.cg_tol, max_iters=s.max_cg_iters)
        return cache, report

    def dense():
        cache, rec = _dense_cache(
            op, y, key, settings, variance_cache=variance_cache
        )
        return cache, rec

    return _run_with_ladder(
        run, settings, context="cache_build", n=n, dense_fn=dense
    )


def _dense_cache(op, y, key, settings, *, variance_cache):
    """Terminal ladder rung for the posterior cache: exact dense state.

    ``basis=eye(n)`` with ``gram_chol=chol(K̂)`` makes ``cached_inv_quad``
    compute the EXACT k*ᵀK̂⁻¹k* — the served variance contract (conservative,
    never undershooting) holds trivially."""
    n = y.shape[-1]
    Kd, L = _dense_chol(op, n)
    t = settings.num_probes
    Z = IdentityPreconditioner().sample_probes(key, t, n).astype(y.dtype)
    rhs = jnp.concatenate([y[:, None], Z], axis=-1)
    X = jnp.linalg.solve(Kd, rhs)
    alpha = X[:, 0]
    cache = PosteriorCache(
        alpha=alpha,
        basis=jnp.eye(n, dtype=jnp.float32) if variance_cache else None,
        gram_chol=L if variance_cache else None,
        probes=Z,
        probe_solves=X[:, 1:],
        precond=IdentityPreconditioner(),
        inv_quad=jnp.dot(y, alpha),
        logdet=2.0 * jnp.sum(jnp.log(jnp.diag(L))),
        cg_iters=jnp.zeros(t + 1, jnp.int32),
    )
    return cache, _dense_rung_record(Kd, rhs, X)


def _compact_basis(basis: jax.Array, gram: jax.Array, max_m: int):
    """Rayleigh–Ritz truncation of a Krylov variance cache to ``max_m``
    columns: diagonalize the small Gram G = QᵀK̂Q = W Λ Wᵀ, keep the top-m
    eigendirections, rotate the basis into them.

    The rotated basis Q·W_m stays orthonormal (orthonormal basis × slim
    orthonormal W), its Gram is exactly diag(Λ_m), and its span is a
    SUBSPACE of the original — so the Galerkin inverse-quad can only
    shrink and the served posterior variance stays conservative at any
    budget; only tightness is traded for the fixed memory."""
    m = gram.shape[0]
    lam, W = jnp.linalg.eigh(gram)  # ascending
    keep = W[:, m - max_m:]
    lam = lam[m - max_m:]
    # eigh of the jittered PSD Gram: floor tiny/negative Ritz values at the
    # same relative jitter scale the full build uses
    lam = jnp.maximum(lam, 1e-6 * jnp.trace(gram) / m)
    return basis @ keep, jnp.diag(jnp.sqrt(lam))


@f32_matmuls
def extend_posterior_cache(
    op: LinearOperator,
    y: jax.Array,
    cache: PosteriorCache,
    settings: BBMMSettings = BBMMSettings(),
) -> PosteriorCache:
    """Incremental PosteriorCache update after data rows were appended.

    ``op``/``y`` are the FULL updated system (old n rows plus k appended
    ones); ``cache`` is the cache built for the first n rows.  Instead of
    re-running the whole (t+1)-column engine block from a cold start, the
    update recycles everything the old cache knows:

      * **warm-started solve** — the old ``alpha`` (zero-padded to n+k) is
        the initial iterate; one single-column mBCG run solves only the
        residual correction K̂'δ = y' − K̂'u₀, whose energy is concentrated
        on the appended rows and their couplings, so it converges in far
        fewer iterations than a from-scratch solve (and reaches the SAME
        final tolerance: the run targets ‖y' − K̂'u‖ ≤ cg_tol·‖y'‖ by
        rescaling ``tol`` with ‖y'‖/‖r₀‖);
      * **Krylov-basis recycling** — the old orthonormal basis, zero-padded
        to the new rows, stays orthonormal, and because the old n×n block
        of K̂' equals the old K̂ exactly, its Gram factor is *reused as is*;
        only the genuinely new directions (the new alpha + the δ-run's
        Lanczos vectors, projected against the recycled span and QR'd) are
        multiplied through the blackbox — O(n²·q) for q ≈ p+1 new columns
        instead of the full build's O(n²·m).  The Galerkin inverse-quad is
        conservative for ANY full-rank basis (it is the infimum of the
        quadratic form over the span), so correctness never depends on how
        stale the recycled directions are — only tightness does.

    The basis grows by ≤ max_cg_iters+1 columns per update; the serving
    layer's ``max_staleness`` policy bounds that growth by forcing a full
    rebuild.  ``logdet`` is NaN on the updated cache (the SLQ estimate is
    not incrementally maintained) and ``probes``/``probe_solves`` are the
    old columns zero-padded — stale diagnostics, unused by serving queries.
    """
    if y.ndim != 1:
        raise ValueError("posterior cache supports a single problem (y of shape (n,))")
    n = y.shape[0]
    n_old = cache.alpha.shape[0]
    k = n - n_old
    if k <= 0:
        raise ValueError(
            f"extend_posterior_cache needs appended rows (cache n={n_old}, y n={n})"
        )
    variance_cache = cache.basis is not None

    def run(s):
        return _extend_cache_once(op, y, cache, s, k=k, variance_cache=variance_cache)

    def dense():
        dcache, rec = _dense_cache(
            op, y, jax.random.PRNGKey(0), settings, variance_cache=variance_cache
        )
        pad_rows = ((0, k), (0, 0))
        # keep the recycled probe diagnostics (stale but shape-stable, like
        # the normal extend path) rather than the fresh dense draws
        dcache = dcache._replace(
            probes=jnp.pad(cache.probes, pad_rows),
            probe_solves=jnp.pad(cache.probe_solves, pad_rows),
            cg_iters=jnp.zeros(1, jnp.int32),
        )
        return dcache, rec

    return _run_with_ladder(
        run, settings, context="cache_extend", n=n, dense_fn=dense
    )


def _extend_cache_once(
    op: LinearOperator,
    y: jax.Array,
    cache: PosteriorCache,
    settings: BBMMSettings,
    *,
    k: int,
    variance_cache: bool,
) -> tuple:
    n = y.shape[0]
    precond = build_preconditioner(
        op, settings.precond_rank, jitter=settings.precond_jitter
    )
    matmul, refresh_kwargs, fused_step = _solver_matmuls(op, settings)
    solver = op.prepare()

    u0 = jnp.pad(cache.alpha, (0, k))
    r0 = y - solver.matmul(u0[:, None])[:, 0]  # f32 true residual
    # mbcg's tol is relative to ‖r0‖; rescale so the TARGET stays
    # ‖y − K̂u‖ ≤ cg_tol·‖y‖ — the same contract as the full build
    norm_y = jnp.linalg.norm(y)
    norm_r0 = jnp.linalg.norm(r0)
    tol_eff = settings.cg_tol * norm_y / jnp.maximum(norm_r0, 1e-30)

    res = mbcg(
        matmul,
        r0[:, None],
        precond_solve=_precond_solve_arg(precond),
        max_iters=settings.max_cg_iters,
        tol=tol_eff,
        return_basis=variance_cache,
        fused_step=fused_step,
        **refresh_kwargs,
    )
    alpha = u0 + res.solves[:, 0]
    inv_quad = jnp.dot(y, alpha)

    basis = gram_chol = None
    if variance_cache:
        B_old = jnp.pad(cache.basis, ((0, k), (0, 0)))  # still orthonormal
        m_old = B_old.shape[1]
        # the basis can hold at most n orthonormal columns; past that the
        # Gram goes singular, so cap the fresh block at the rank budget
        # (q_cap == 0 ⇒ the recycled span is already full-dimensional and
        # the old factor serves as is — conservativeness is unaffected)
        q_cap = max(n - m_old, 0)
        if q_cap == 0:
            basis, gram_chol = B_old, cache.gram_chol
        else:
            fresh = jnp.concatenate(
                [alpha[:, None], res.basis.reshape(n, -1)], axis=-1
            ).astype(jnp.float32)
            # project out the recycled span, orthonormalize the remainder
            fresh = fresh - B_old @ (B_old.T @ fresh)
            N = jnp.linalg.qr(fresh)[0][:, :q_cap]  # (n, q)
            KN = solver.matmul(N)  # blackbox matmul on q ≪ m columns only
            # old Gram block recycled exactly: the padded basis hits only
            # the old n×n block of K̂', which is the old K̂ — CᵀC already
            # includes its jitter, and overstating the Gram only makes the
            # served variance MORE conservative
            top = cache.gram_chol @ cache.gram_chol.T
            cross = B_old.T @ KN  # (m, q)
            low = N.T @ KN
            low = 0.5 * (low + low.T)
            q = low.shape[0]
            jitter = 1e-6 * jnp.trace(low) / q
            gram = jnp.block(
                [[top, cross],
                 [cross.T, low + jitter * jnp.eye(q, dtype=low.dtype)]]
            )
            basis = jnp.concatenate([B_old, N], axis=-1)
            gram_chol = jnp.linalg.cholesky(gram)
        # Krylov basis compaction: under a serving memory budget the
        # recycled basis must stop growing by ~p+1 columns per append —
        # Rayleigh–Ritz truncate to the top-m eigendirections of the small
        # Gram (conservative for any budget; see _compact_basis)
        max_m = settings.max_basis_columns
        if max_m and basis.shape[1] > max_m:
            gram_full = gram_chol @ gram_chol.T
            basis, gram_chol = _compact_basis(
                basis.astype(jnp.float32), gram_full.astype(jnp.float32), max_m
            )

    pad_rows = ((0, k), (0, 0))
    new_cache = PosteriorCache(
        alpha=alpha,
        basis=basis,
        gram_chol=gram_chol,
        probes=jnp.pad(cache.probes, pad_rows),
        probe_solves=jnp.pad(cache.probe_solves, pad_rows),
        precond=precond,
        inv_quad=inv_quad,
        logdet=jnp.float32(jnp.nan),
        cg_iters=res.num_iters,
    )
    # classify against the tolerance actually in force (tol_eff), and on the
    # FULL warm-started iterate — the delta-solve alone can be finite while
    # u0 + delta is what callers consume
    report = classify_mbcg(
        res, tol_eff, max_iters=settings.max_cg_iters, solution=alpha
    )
    return new_cache, report


@f32_matmuls
def cached_mean(cache: PosteriorCache, Kxs: jax.Array) -> jax.Array:
    """Posterior mean k(X*, X) K̂⁻¹y from the cache — O(n·s), no CG."""
    return Kxs.T @ cache.alpha


@f32_matmuls
def cached_inv_quad(cache: PosteriorCache, Kxs: jax.Array) -> jax.Array:
    """k*ᵀK̂⁻¹k* per column of Kxs via the Rayleigh–Ritz cache — O(n·m)."""
    if cache.basis is None:
        raise ValueError(
            "cache was built with variance_cache=False; rebuild with "
            "variance_cache=True for variance queries"
        )
    v = cache.basis.T @ Kxs  # (m, s)
    w = jax.scipy.linalg.cho_solve((cache.gram_chol, True), v)
    return jnp.sum(v * w, axis=0)


def marginal_log_likelihood(
    op: LinearOperator,
    y: jax.Array,
    key: jax.Array,
    settings: BBMMSettings = BBMMSettings(),
):
    """GP marginal log likelihood  −½(yᵀK̂⁻¹y + log|K̂| + n·log 2π)  (Eq. 2).

    Differentiable w.r.t. every array leaf of ``op`` (kernel hyperparameters,
    noise, inducing points, deep-kernel network weights, ...) and ``y``.
    Batched ``y`` (b, n) → (b,) MLLs from one fused engine call.
    """
    n = y.shape[-1]
    inv_quad, logdet = inv_quad_logdet(op, y, key, settings)
    return -0.5 * (inv_quad + logdet + n * jnp.log(2.0 * jnp.pi))


@f32_matmuls
def solve(op, B, settings: BBMMSettings = BBMMSettings(), *, precond=None):
    """Plain preconditioned solve K̂⁻¹B (prediction-time helper).

    ``precond``: a prebuilt preconditioner (e.g. ``PosteriorCache.precond``)
    to reuse instead of rebuilding the pivoted-Cholesky factors.  Health-
    checked per ``settings.on_failure``; ladder rungs rebuild the
    preconditioner for their own settings."""
    B = jnp.asarray(B)
    n = B.shape[-2] if B.ndim > 1 else B.shape[-1]

    def run(s):
        p = precond
        if p is None or s is not settings:
            p = build_preconditioner(
                op, s.precond_rank, jitter=s.precond_jitter
            )
        matmul, refresh_kwargs, fused_step = _solver_matmuls(op, s)
        res = mbcg(
            matmul,
            B,
            precond_solve=_precond_solve_arg(p),
            max_iters=s.max_cg_iters,
            tol=s.cg_tol,
            fused_step=fused_step,
            **refresh_kwargs,
        )
        report = classify_mbcg(res, s.cg_tol, max_iters=s.max_cg_iters)
        return res.solves, report

    def dense():
        Kd, L = _dense_chol(op, n)
        rhs = B[..., None] if B.ndim == 1 else B
        X = jnp.linalg.solve(Kd, rhs)
        out = X[..., 0] if B.ndim == 1 else X
        return out, _dense_rung_record(Kd, rhs, X)

    return _run_with_ladder(run, settings, context="solve", n=n, dense_fn=dense)
