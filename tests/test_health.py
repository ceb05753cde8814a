"""Solve-health taxonomy, degradation ladder, fault injection, serving
hardening (the robustness ISSUE).

Covers the acceptance criteria:
  * every taxonomy status is reached through a REAL mBCG solve driven by
    :class:`FaultInjectingOperator` (seeded, deterministic) — not by
    hand-built telemetry;
  * under ``on_failure="degrade"`` each ladder rung fires exactly once,
    records itself in ``SolveReport.rungs``, and the terminal dense
    Cholesky heals an otherwise-unhealable injected solve;
  * circuit-breaker state transitions are deterministic under an
    injectable clock;
  * a degraded query (breaker open) is BITWISE equal to the last
    consistent cache's answer;
  * non-finite inputs are rejected with actionable errors before any
    session/fit mutation;
  * ``fit_gp`` trains ``mode="pallas"`` through the kernel's custom VJP
    and never switches to dense training;
  * the end-to-end ``--chaos`` threaded drill completes with zero
    unhandled exceptions, >=1 precision escalation, >=1 degraded query.
"""

import threading
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AddedDiagOperator,
    BBMMSettings,
    DenseOperator,
    FaultInjectingOperator,
    FaultSchedule,
    SolveFailure,
    SolveHealthWarning,
    collect,
    solve,
)
from repro.core import health
from repro.gp import ExactGP, fit_gp
from repro.launch.gp_serve import _ChaosModel, run_serve_chaos
from repro.serving import (
    CircuitBreaker,
    PosteriorSession,
    QueryDeadlineExceeded,
)

jax.config.update("jax_platform_name", "cpu")

pytestmark = pytest.mark.robust

N = 48


@pytest.fixture(scope="module")
def system():
    """One fixed SPD system shared by the taxonomy/ladder tests."""
    key = jax.random.PRNGKey(0)
    Q = jax.random.normal(key, (N, N)) / jnp.sqrt(N)
    A = Q @ Q.T
    b = jax.random.normal(jax.random.fold_in(key, 1), (N,))
    return A, b


def injected_op(A, schedule=None, negative_diag=0.0, sigma2=0.1):
    sched = FaultSchedule(0) if schedule is None else schedule
    return AddedDiagOperator(
        FaultInjectingOperator(
            DenseOperator(A), schedule=sched, negative_diag=negative_diag
        ),
        jnp.float32(sigma2),
    )


def solve_report(op, b, settings):
    """Run solve() under a collector; return (last report, solution)."""
    with collect() as reports:
        x = solve(op, b, settings)
    assert reports, "eager solve must record a SolveReport"
    return reports[-1], x


MIXED = BBMMSettings(
    num_probes=4, max_cg_iters=8, cg_tol=1e-6, precond_rank=0,
    precision="mixed", cg_refresh_every=2,
)
HIGHEST = BBMMSettings(num_probes=4, max_cg_iters=10, cg_tol=1e-6, precond_rank=0)


class TestTaxonomy:
    """Each failure class, reached via FaultInjectingOperator."""

    def test_converged_clean(self, system):
        A, b = system
        s = BBMMSettings(num_probes=4, max_cg_iters=60, cg_tol=1e-4)
        rep, x = solve_report(injected_op(A), b, s)
        assert rep.status == health.CONVERGED
        assert rep.healthy and not rep.degraded
        assert rep.residual_norm <= rep.tol
        assert bool(jnp.all(jnp.isfinite(x)))
        assert [r.rung for r in rep.rungs] == ["initial"]

    def test_max_iters_budget_exhausted(self, system):
        A, b = system
        s = BBMMSettings(num_probes=4, max_cg_iters=2, cg_tol=1e-10)
        with pytest.warns(SolveHealthWarning):
            rep, _ = solve_report(injected_op(A), b, s)
        assert rep.status == health.MAX_ITERS
        assert rep.num_iters == rep.max_iters == 2
        assert rep.residual_norm > rep.tol

    def test_non_finite_total_outage(self, system):
        A, b = system
        sched = FaultSchedule(0, total_outage=True)
        with pytest.warns(SolveHealthWarning):
            rep, x = solve_report(injected_op(A, sched), b, HIGHEST)
        assert rep.status == health.NON_FINITE
        assert not bool(jnp.all(jnp.isfinite(x)))

    def test_rescued_inf_on_refresh_matmul(self, system):
        # an Inf landing in the f32 residual-refresh matmul trips the
        # non-finite rescue (pull + restart); the solve survives but the
        # contamination is on the record
        A, b = system
        sched = FaultSchedule(0, inf_calls=(2,))
        with pytest.warns(SolveHealthWarning):
            rep, x = solve_report(injected_op(A, sched), b, MIXED)
        assert rep.status == health.RESCUED
        assert rep.num_rescues >= 1
        assert bool(jnp.all(jnp.isfinite(x)))
        assert sched.injected == [(2, FaultSchedule.INF)]

    def test_stalled_curvature_guard(self, system):
        # an Inf in the CG-loop matmul makes d'Kd non-finite -> the
        # curvature guard freezes the column (counted) instead of updating
        A, b = system
        sched = FaultSchedule(0, inf_calls=(4,))
        with pytest.warns(SolveHealthWarning):
            rep, x = solve_report(injected_op(A, sched), b, MIXED)
        assert rep.status == health.STALLED
        assert rep.num_curvature_skips >= 1
        assert bool(jnp.all(jnp.isfinite(x)))

    def test_diverged_non_psd_perturbation(self, system):
        # negative_diag shifts eigenvalues negative: CG on the indefinite
        # system walks AWAY from the solution — finite, but worse than the
        # zero initial guess
        A, b = system
        with pytest.warns(SolveHealthWarning):
            rep, x = solve_report(
                injected_op(A, negative_diag=0.3), b, HIGHEST
            )
        assert rep.status == health.DIVERGED
        assert rep.residual_norm > health.DIVERGENCE_GATE
        assert bool(jnp.all(jnp.isfinite(x)))

    def test_schedule_is_deterministic(self, system):
        A, b = system
        logs = []
        for _ in range(2):
            sched = FaultSchedule(7, nan_rate=0.3)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", SolveHealthWarning)
                solve_report(injected_op(A, sched), b, MIXED)
            logs.append((sched.calls, tuple(sched.injected)))
        assert logs[0] == logs[1]

    def test_classification_noop_inside_jit(self, system):
        # tracer-safe: the jitted path compiles and runs with no report
        A, b = system
        op = injected_op(A)

        @jax.jit
        def f(b):
            return solve(op, b, HIGHEST)

        with collect() as reports:
            x = f(b)
        assert bool(jnp.all(jnp.isfinite(x)))
        assert reports == []


class TestDegradationLadder:
    def test_precision_escalation_heals(self, system):
        # faults only in the reduced-precision path: the first rung
        # (precision_f32) must heal it — and the report says so
        A, b = system
        sched = FaultSchedule(0, nan_rate=1.0, reduced_only=True)
        s = BBMMSettings(
            num_probes=4, max_cg_iters=60, cg_tol=1e-4, precond_rank=0,
            precision="mixed", on_failure="degrade",
        )
        with pytest.warns(SolveHealthWarning, match="degraded but healed"):
            rep, x = solve_report(injected_op(A, sched), b, s)
        assert rep.status == health.CONVERGED
        assert rep.degraded
        assert [r.rung for r in rep.rungs] == ["initial", "precision_f32"]
        assert bool(jnp.all(jnp.isfinite(x)))

    def test_every_rung_fires_once_and_dense_heals(self, system):
        # faults at EVERY precision (matmul only): no iterative rung can
        # heal, so the ladder walks end to end and the terminal dense
        # Cholesky (clean to_dense) answers
        A, b = system
        sched = FaultSchedule(0, nan_rate=1.0)
        s = BBMMSettings(
            num_probes=4, max_cg_iters=4, cg_tol=1e-6, precond_rank=0,
            precision="mixed", fuse_cg=True, on_failure="degrade",
        )
        with pytest.warns(SolveHealthWarning, match="dense Cholesky"):
            rep, x = solve_report(injected_op(A, sched), b, s)
        rungs = [r.rung for r in rep.rungs]
        assert rungs == [
            "initial", "precision_f32", "unfused", "extend_budget",
            "dense_cholesky",
        ]
        assert len(rungs) == len(set(rungs))  # each rung exactly once
        assert rep.status == health.CONVERGED
        # the dense answer really solves the (clean) system
        K = A + 0.1 * jnp.eye(N)
        res = jnp.linalg.norm(K @ x - b) / jnp.linalg.norm(b)
        assert float(res) < 1e-3

    def test_noop_rungs_are_skipped(self, system):
        # already f32 + already unfused: the ladder goes straight to
        # extend_budget, then dense
        A, b = system
        sched = FaultSchedule(0, nan_rate=1.0)
        s = BBMMSettings(
            num_probes=4, max_cg_iters=4, cg_tol=1e-6, precond_rank=0,
            on_failure="degrade",
        )
        with pytest.warns(SolveHealthWarning):
            rep, _ = solve_report(injected_op(A, sched), b, s)
        assert [r.rung for r in rep.rungs] == [
            "initial", "extend_budget", "dense_cholesky",
        ]

    def test_ladder_exhausted_raises(self, system):
        # total outage corrupts to_dense too: nothing can heal -> the
        # ladder raises SolveFailure with the full rung trail attached
        A, b = system
        sched = FaultSchedule(0, total_outage=True)
        s = BBMMSettings(
            num_probes=4, max_cg_iters=4, cg_tol=1e-6, precond_rank=0,
            on_failure="degrade",
        )
        with pytest.raises(SolveFailure) as ei:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", SolveHealthWarning)
                solve(injected_op(A, sched), b, s)
        rungs = [r.rung for r in ei.value.report.rungs]
        assert rungs[0] == "initial" and rungs[-1] == "dense_cholesky"

    def test_on_failure_raise(self, system):
        A, b = system
        sched = FaultSchedule(0, total_outage=True)
        s = BBMMSettings(
            num_probes=4, max_cg_iters=4, precond_rank=0, on_failure="raise"
        )
        with pytest.raises(SolveFailure):
            solve(injected_op(A, sched), b, s)

    def test_dense_fallback_gated_by_n(self, system):
        A, b = system
        sched = FaultSchedule(0, nan_rate=1.0)
        s = BBMMSettings(
            num_probes=4, max_cg_iters=4, precond_rank=0,
            on_failure="degrade", dense_fallback_max_n=N - 1,
        )
        with pytest.raises(SolveFailure):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", SolveHealthWarning)
                solve(injected_op(A, sched), b, s)

    def test_invalid_policy_rejected(self):
        with pytest.raises(ValueError, match="on_failure"):
            BBMMSettings(on_failure="panic")


class TestCircuitBreaker:
    def test_deterministic_transitions(self):
        t = [0.0]
        br = CircuitBreaker(threshold=2, reset_after_s=10.0, clock=lambda: t[0])
        assert br.allow() and br.state == CircuitBreaker.CLOSED
        br.record_failure()
        assert br.state == CircuitBreaker.CLOSED  # under threshold
        br.record_failure()
        assert br.state == CircuitBreaker.OPEN
        assert not br.allow()  # cool-down not elapsed
        t[0] = 9.9
        assert not br.allow()
        t[0] = 10.0
        assert br.allow() and br.state == CircuitBreaker.HALF_OPEN
        br.record_failure()  # half-open trial fails -> re-open
        assert br.state == CircuitBreaker.OPEN
        t[0] = 25.0
        assert br.allow() and br.state == CircuitBreaker.HALF_OPEN
        br.record_success()
        assert br.state == CircuitBreaker.CLOSED and br.failures == 0
        assert [(a, c) for a, c, _ in br.transitions] == [
            ("closed", "open"),
            ("open", "half_open"),
            ("half_open", "open"),
            ("open", "half_open"),
            ("half_open", "closed"),
        ]

    def test_success_resets_failure_count(self):
        br = CircuitBreaker(threshold=3, clock=lambda: 0.0)
        br.record_failure()
        br.record_failure()
        br.record_success()
        br.record_failure()
        br.record_failure()
        assert br.state == CircuitBreaker.CLOSED  # never 3 consecutive


def _session_fixture(n=40, **kw):
    key = jax.random.PRNGKey(3)
    kx, ky = jax.random.split(key)
    X = jax.random.uniform(kx, (n, 2)) * 2 - 1
    y = jnp.sin(3 * X[:, 0]) + 0.05 * jax.random.normal(ky, (n,))
    gp = ExactGP(
        settings=BBMMSettings(
            num_probes=4, max_cg_iters=40, on_failure="degrade"
        ),
        precision="mixed",
    )
    sched = FaultSchedule(0, reduced_only=True)
    chaos = _ChaosModel(gp, sched)
    sess = PosteriorSession(chaos, gp.init_params(X), X, y, **kw)
    return sess, sched, X, y


class TestServingHardening:
    def test_degraded_query_bitwise_equal_to_last_consistent(self):
        sess, sched, X, y = _session_fixture(
            breaker_threshold=1, breaker_reset_s=1e6, rebuild_retries=0
        )
        Xq = X[:5] + 0.01
        mean0, var0 = sess.query(Xq)
        # outage + a params nudge: the cache is stale and unrebuildable
        sched.total_outage = True
        sess.update_params(
            jax.tree_util.tree_map(lambda p: p + 1e-6, sess.params)
        )
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", SolveHealthWarning)
            mean1, var1 = sess.query(Xq)  # trips the breaker, degrades
            mean2, var2 = sess.query(Xq)  # breaker already open
        assert sess.breaker.state == CircuitBreaker.OPEN
        assert sess.degraded_queries >= 2
        assert sess.cache_info.degraded
        for m, v in ((mean1, var1), (mean2, var2)):
            np.testing.assert_array_equal(np.asarray(m), np.asarray(mean0))
            np.testing.assert_array_equal(np.asarray(v), np.asarray(var0))

    def test_breaker_recovery_clears_degraded_flag(self):
        sess, sched, X, _ = _session_fixture(
            breaker_threshold=1, breaker_reset_s=0.0, rebuild_retries=0
        )
        Xq = X[:5]
        sched.total_outage = True
        sess.update_params(
            jax.tree_util.tree_map(lambda p: p + 1e-6, sess.params)
        )
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", SolveHealthWarning)
            sess.query(Xq)
        assert sess.breaker.state == CircuitBreaker.OPEN
        sched.total_outage = False  # fault clears; reset_after_s=0 ->
        sess.query(Xq)  # half-open trial succeeds immediately
        assert sess.breaker.state == CircuitBreaker.CLOSED
        assert not sess.cache_info.degraded
        assert not sess.stale()

    def test_query_deadline_degrades_then_raises_without_cache(self):
        sess, _, X, y = _session_fixture(query_deadline_s=0.05)
        Xq = X[:3]
        mean0, _ = sess.query(Xq)
        # hold the rebuild gate so admission cannot proceed, and stale the
        # cache so the query NEEDS admission
        sess.update_params(
            jax.tree_util.tree_map(lambda p: p + 1e-6, sess.params)
        )
        with sess._rebuild_gate:
            mean1, _ = sess.query(Xq)  # deadline -> degraded fallback
            assert sess.degraded_queries >= 1
            np.testing.assert_array_equal(np.asarray(mean1), np.asarray(mean0))
            # a session with NO consistent cache ever built must raise
            fresh = PosteriorSession(
                sess.model, sess.params, X, y, build=False,
                query_deadline_s=0.05,
            )
            fresh._rebuild_gate = sess._rebuild_gate  # shared held gate
            with pytest.raises(QueryDeadlineExceeded):
                fresh.query(Xq)

    def test_observe_rejects_non_finite_before_mutation(self):
        sess, _, X, _ = _session_fixture()
        n0, v0 = sess.n, sess.cache_info.version
        bad_y = jnp.array([jnp.nan])
        with pytest.raises(ValueError, match="non-finite"):
            sess.observe(X[:1] + 0.5, bad_y)
        bad_X = jnp.array([[jnp.inf, 0.0]])
        with pytest.raises(ValueError, match="non-finite"):
            sess.observe(bad_X, jnp.array([0.1]))
        assert sess.n == n0 and sess.cache_info.version == v0
        assert not sess.stale()  # session intact, still serving

    def test_init_rejects_non_finite(self):
        gp = ExactGP(settings=BBMMSettings(num_probes=4, max_cg_iters=10))
        X = jnp.ones((4, 2)).at[2, 1].set(jnp.nan)
        y = jnp.ones((4,))
        with pytest.raises(ValueError, match="non-finite"):
            PosteriorSession(gp, gp.init_params(X), X, y)

    def test_observe_failure_counts_with_breaker(self):
        sess, sched, X, _ = _session_fixture(
            breaker_threshold=1, breaker_reset_s=1e6, rebuild_retries=0,
            max_staleness=0,  # every observe is a guarded rebuild
        )
        sched.total_outage = True
        with pytest.raises(Exception):
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", SolveHealthWarning)
                sess.observe(X[:1] + 0.3, jnp.array([0.2]))
        assert sess.rebuild_failures == 1
        assert sess.breaker.state == CircuitBreaker.OPEN
        stats = sess.health_stats()
        assert stats["rebuild_failures"] == 1
        assert stats["breaker_state"] == CircuitBreaker.OPEN


class TestFitGP:
    def test_rejects_non_finite_inputs(self):
        gp = ExactGP(settings=BBMMSettings(num_probes=2, max_cg_iters=5))
        X = jnp.ones((6, 1))
        y = jnp.zeros((6,)).at[3].set(jnp.inf)
        with pytest.raises(ValueError, match="y contains 1 non-finite"):
            fit_gp(gp, X, y, steps=1)
        with pytest.raises(ValueError, match="X contains"):
            fit_gp(gp, X.at[0, 0].set(jnp.nan), jnp.zeros((6,)), steps=1)

    def test_pallas_trains_through_kernel_without_dense_fallback(self):
        """mode="pallas" MLL gradients (custom VJP around the Pallas
        launch) match mode="dense", and fit_gp keeps the pallas model
        through every step — no warning, no switch to a dense K."""
        X = jax.random.uniform(jax.random.PRNGKey(0), (48, 2))
        y = jnp.sin(4 * X[:, 0]) + 0.5 * jnp.cos(3 * X[:, 1])
        s = BBMMSettings(num_probes=4, max_cg_iters=60, cg_tol=1e-6, precond_rank=3)
        key = jax.random.PRNGKey(1)
        grads = {}
        for mode in ("dense", "pallas"):
            gp = ExactGP(kernel_type="matern52", mode=mode, ard=True, settings=s)
            grads[mode] = jax.grad(gp.loss)(gp.init_params(X), X, y, key)
        for k in grads["dense"]:
            np.testing.assert_allclose(
                np.asarray(grads["pallas"][k]), np.asarray(grads["dense"][k]),
                rtol=1e-3, atol=1e-4,
            )

        traced_modes = []

        class ModeSpy(ExactGP):
            def loss(self, params, data, y, key):
                traced_modes.append(self.mode)
                return super().loss(params, data, y, key)

        with warnings.catch_warnings():
            warnings.simplefilter("error", SolveHealthWarning)
            params, hist = fit_gp(ModeSpy(mode="pallas", settings=s), X, y, steps=3)
        assert traced_modes and set(traced_modes) == {"pallas"}, traced_modes
        assert len(hist) == 3 and all(np.isfinite(h) for h in hist)
        assert all(
            bool(jnp.all(jnp.isfinite(v)))
            for v in jax.tree_util.tree_leaves(params)
        )


class TestChaosDrill:
    def test_threaded_chaos_drill_end_to_end(self):
        metrics = run_serve_chaos(
            n=48, batch=8, requests_per_phase=3, threads=2,
            max_cg_iters=25, breaker_reset_s=0.2, verbose=False,
        )
        assert metrics["unhandled_exceptions"] == 0
        assert metrics["precision_escalations"] >= 1
        assert metrics["degraded_queries"] >= 1
        assert metrics["breaker_state"] == CircuitBreaker.CLOSED
        assert metrics["fault_injected"] >= 1
        assert metrics["chaos_ok"]
