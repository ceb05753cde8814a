"""Partitioned kernel MVMs: row-panel streaming for million-row exact GPs.

The memory contract under test: ``mode="pallas_partitioned"`` never
materializes K — every matmul streams (panel_rows × n) row-panels (Pallas
``row_offset`` launches or checkpointed XLA tiles), asserted through the
``panel_accounting`` hook.  Covers panel-vs-dense parity (odd n, panel
sizes that don't divide n, batched RHS), checkpointed MLL gradients vs the
in-memory path, shard_map panel bands bitwise-equal to single-device on 8
forced CPU devices, a real n=20 000 engine solve + posterior cache build,
dense_direct small-n routing, and single-panel fault injection healing
through the PR 6 degradation ladder.

PR 8 makes ``fuse_cg=True`` real on this path: the PANEL-FUSED CG step —
one fused kernel launch per row-panel per iteration, the [dᵀV; rᵀr; rᵀV;
vᵀV] reductions carried across the panel loop — is tested for parity with
the unfused streamed loop (solves, logdet, MLL grads) on both backends,
for jaxpr-counted launches == num_panels with no (n, n) aval anywhere,
for bitwise 1-vs-8-device equality (deterministic ordered reduction
fold), for the band-sharded custom-VJP backward (all devices re-stream
their own gradient panels; also unblocks pallas-backend sharded grads),
and for chaos confinement + ladder healing on the fused path.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap
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
    PartitionedKernelOperator,
    SolveHealthWarning,
    build_posterior_cache,
    collect,
    engine_state,
    panel_accounting,
    solve,
)
from repro.gp import ExactGP, KernelOperator, RBFKernel
from repro.kernels.kernel_matmul.ops import (
    MAX_PANEL_ROWS,
    PANEL_ALIGN,
    choose_panel_rows,
)

pytestmark = pytest.mark.partitioned

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _problem(n, d=4, seed=0):
    X = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
    kern = RBFKernel(lengthscale=jnp.float32(0.7), outputscale=jnp.float32(1.3))
    return X, kern


class TestPanelChooser:
    def test_budget_bound_and_alignment(self):
        for n in (100, 1_000, 20_000, 100_000, 1_000_000):
            p = choose_panel_rows(n)
            assert p % PANEL_ALIGN == 0
            assert p <= MAX_PANEL_ROWS
            # within budget unless clamped at the alignment floor
            assert p == PANEL_ALIGN or p * n * 4 <= 128 * 1024 * 1024

    def test_monotone_in_budget(self):
        small = choose_panel_rows(50_000, budget_bytes=8 << 20)
        large = choose_panel_rows(50_000, budget_bytes=512 << 20)
        assert small <= large

    def test_small_n_clamps_to_n(self):
        # panel never needs to exceed the (aligned) matrix height
        assert choose_panel_rows(200) <= 256

    def test_invalid(self):
        with pytest.raises(ValueError):
            choose_panel_rows(0)
        with pytest.raises(ValueError):
            choose_panel_rows(100, budget_bytes=0)

    def test_fused_budget_accounts_cg_state(self):
        """fused=True budgets the fused step's working set — the kernel slab
        PLUS the f32 row-state slabs per panel and the resident column state
        + (4, t) reduction slab — so the chosen panel shrinks vs the plain
        chooser and the fused working set still fits the budget."""
        from repro.kernels.kernel_matmul.kernel_matmul import _FUSED_STATE_SLABS

        n, t, b = 50_000, 128, 4
        budget = 512 << 20
        plain = choose_panel_rows(n, budget_bytes=budget)
        fused = choose_panel_rows(
            n, budget_bytes=budget, rhs_cols=t, batch=b, fused=True
        )
        assert fused % PANEL_ALIGN == 0
        assert fused < plain
        per_row = n * 4 + _FUSED_STATE_SLABS * b * t * 4
        overhead = 3 * n * b * t * 4 + 4 * t * 4
        assert fused == PANEL_ALIGN or fused * per_row + overhead <= budget
        # without fused=True the extra shape hints change nothing (the plain
        # matmul path is byte-identical to the pre-fused chooser)
        assert choose_panel_rows(n, budget_bytes=budget, rhs_cols=t, batch=b) == plain


class TestPanelParity:
    """Panel-vs-dense matmul/diagonal/row parity ≤ 1e-4: odd n, panel sizes
    that don't divide n, batched RHS — both backends."""

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    @pytest.mark.parametrize("n,panel_rows", [(773, 256), (257, 100)])
    def test_matmul_matches_dense(self, backend, n, panel_rows):
        X, kern = _problem(n)
        dense = KernelOperator(kernel=kern, X=X, mode="dense")
        op = PartitionedKernelOperator(
            kernel=kern, X=X, panel_rows=panel_rows, backend=backend
        )
        M = jax.random.normal(jax.random.PRNGKey(1), (n, 3))
        np.testing.assert_allclose(
            np.asarray(op.matmul(M)), np.asarray(dense.matmul(M)),
            rtol=1e-4, atol=1e-4,
        )
        # vector RHS
        np.testing.assert_allclose(
            np.asarray(op.matmul(M[:, 0])), np.asarray(dense.matmul(M[:, 0])),
            rtol=1e-4, atol=1e-4,
        )

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_batched_rhs(self, backend):
        n = 353
        X, kern = _problem(n)
        dense = KernelOperator(kernel=kern, X=X, mode="dense")
        op = PartitionedKernelOperator(
            kernel=kern, X=X, panel_rows=128, backend=backend
        )
        B = jax.random.normal(jax.random.PRNGKey(2), (2, n, 3))
        ref = jnp.stack([dense.matmul(B[i]) for i in range(2)])
        np.testing.assert_allclose(
            np.asarray(op.matmul(B)), np.asarray(ref), rtol=1e-4, atol=1e-4
        )

    def test_row_diagonal_exact(self):
        n = 311
        X, kern = _problem(n)
        dense = KernelOperator(kernel=kern, X=X, mode="dense")
        op = PartitionedKernelOperator(kernel=kern, X=X, panel_rows=64)
        np.testing.assert_allclose(
            np.asarray(op.diagonal()), np.asarray(dense.diagonal()),
            rtol=1e-6, atol=1e-6,
        )
        np.testing.assert_allclose(
            np.asarray(op.row(17)), np.asarray(dense.row(17)),
            rtol=1e-6, atol=1e-6,
        )

    def test_kernel_operator_mode_threads_through(self):
        n = 300
        X, kern = _problem(n)
        ko = KernelOperator(
            kernel=kern, X=X, mode="pallas_partitioned", panel_rows=128
        )
        prepared = ko.prepare()
        assert isinstance(prepared, PartitionedKernelOperator)
        M = jax.random.normal(jax.random.PRNGKey(1), (n, 2))
        ref = KernelOperator(kernel=kern, X=X, mode="dense").matmul(M)
        np.testing.assert_allclose(
            np.asarray(ko.matmul(M)), np.asarray(ref), rtol=1e-4, atol=1e-4
        )


class TestAccounting:
    def test_no_full_height_panel_ever(self):
        """The memory-contract hook: every recorded launch streams panels
        strictly shorter than n — no n×n working set on the partitioned
        path."""
        n = 1031
        X, kern = _problem(n)
        op = AddedDiagOperator(
            KernelOperator(
                kernel=kern, X=X, mode="pallas_partitioned", panel_rows=256
            ),
            0.5,
        )
        y = jnp.sin(X[:, 0])
        s = BBMMSettings(num_probes=2, max_cg_iters=5, precond_rank=0, cg_tol=0.3)
        with panel_accounting() as launches:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                engine_state(op, y, jax.random.PRNGKey(0), s)
        assert launches, "partitioned matmul recorded no panel launches"
        for lau in launches:
            assert lau.panel_rows < lau.n
            assert lau.panel_bytes < lau.dense_bytes
            assert lau.num_panels == -(-lau.n // lau.panel_rows)

    def test_accounting_is_scoped(self):
        n = 300
        X, kern = _problem(n)
        op = PartitionedKernelOperator(kernel=kern, X=X, panel_rows=128)
        M = jnp.ones((n, 1))
        with panel_accounting() as launches:
            op.matmul(M)
        count = len(launches)
        op.matmul(M)  # outside the context: not recorded
        assert len(launches) == count


class TestGradients:
    def test_checkpointed_mll_grad_matches_dense(self):
        """Grad parity of the checkpointed panel-streamed MLL vs the
        in-memory dense path (the fit_gp memory story)."""
        n = 192
        X = jax.random.normal(jax.random.PRNGKey(0), (n, 4))
        y = jnp.sin(X[:, 0]) + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (n,))
        key = jax.random.PRNGKey(2)
        s = BBMMSettings(num_probes=4, max_cg_iters=40, precond_rank=0, panel_rows=64)
        gp_part = ExactGP(mode="pallas_partitioned", settings=s)
        gp_dense = ExactGP(mode="dense", settings=s)
        params = gp_part.init_params(X)
        lp, g_part = jax.value_and_grad(gp_part.loss)(params, X, y, key)
        ld, g_dense = jax.value_and_grad(gp_dense.loss)(params, X, y, key)
        np.testing.assert_allclose(float(lp), float(ld), rtol=1e-4)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(g_part[k]), np.asarray(g_dense[k]), rtol=2e-3, atol=1e-4
            )

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_custom_vjp_both_backends(self, backend):
        """The custom VJP differentiates the pallas forward too (jax never
        sees the pallas_call — the interpret-mode jvp gap is bypassed)."""
        n = 160
        X, _ = _problem(n)
        M = jax.random.normal(jax.random.PRNGKey(1), (n, 2))

        def loss(ell, backend):
            kern = RBFKernel(lengthscale=ell, outputscale=jnp.float32(1.3))
            op = PartitionedKernelOperator(
                kernel=kern, X=X, panel_rows=64, backend=backend
            )
            return jnp.sum(op.matmul(M) ** 2)

        def loss_dense(ell):
            kern = RBFKernel(lengthscale=ell, outputscale=jnp.float32(1.3))
            return jnp.sum(
                KernelOperator(kernel=kern, X=X, mode="dense").matmul(M) ** 2
            )

        g = jax.grad(loss)(jnp.float32(0.7), backend)
        g_ref = jax.grad(loss_dense)(jnp.float32(0.7))
        np.testing.assert_allclose(float(g), float(g_ref), rtol=1e-4)

    def test_fit_gp_trains_natively(self):
        """mode='pallas_partitioned' trains WITHOUT the PR 6 dense degrade
        (no pallas-jvp gap on the custom-VJP path)."""
        n = 128
        X = jax.random.normal(jax.random.PRNGKey(0), (n, 3))
        y = jnp.sin(X @ jnp.ones(3))
        s = BBMMSettings(num_probes=2, max_cg_iters=10, precond_rank=0, panel_rows=64)
        gp = ExactGP(mode="pallas_partitioned", settings=s)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            params, history = gp.fit(X, y, steps=2, lr=0.05, key=jax.random.PRNGKey(3))
        assert not any("dense" in str(x.message).lower() and "degrad" in
                       str(x.message).lower() for x in w)
        assert np.isfinite(np.asarray(history)).all()


class TestSharded:
    def test_shard_map_bitwise_equal_single_device(self):
        """8-CPU-device panel bands vs single-device streaming: bitwise."""
        body = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import PartitionedKernelOperator, panel_accounting
        from repro.gp import RBFKernel

        assert jax.device_count() == 8
        n = 768
        X = jax.random.normal(jax.random.PRNGKey(0), (n, 4))
        kern = RBFKernel(lengthscale=jnp.float32(0.7), outputscale=jnp.float32(1.3))
        M = jax.random.normal(jax.random.PRNGKey(1), (n, 3))
        mesh = make_mesh((8,), ("data",))
        for backend in ("pallas", "xla"):
            single = PartitionedKernelOperator(
                kernel=kern, X=X, panel_rows=100, backend=backend, data_axes=())
            ref = single.matmul(M)
            sharded = PartitionedKernelOperator(
                kernel=kern, X=X, panel_rows=100, backend=backend, mesh=mesh)
            with panel_accounting() as launches:
                out = sharded.matmul(M)
            assert launches[0].sharded and launches[0].devices == 8, launches
            assert np.array_equal(np.asarray(out), np.asarray(ref)), (
                backend, float(jnp.max(jnp.abs(out - ref))))
        print("OK")
        """
        self._run(body)

    def test_ambient_mesh_context_shards(self):
        body = """
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import PartitionedKernelOperator, panel_accounting
        from repro.gp import RBFKernel

        n = 512
        X = jax.random.normal(jax.random.PRNGKey(0), (n, 4))
        kern = RBFKernel(lengthscale=jnp.float32(0.7), outputscale=jnp.float32(1.3))
        M = jax.random.normal(jax.random.PRNGKey(1), (n, 2))
        op = PartitionedKernelOperator(kernel=kern, X=X, panel_rows=64, backend="xla")
        ref = op.matmul(M)  # no mesh resolvable: single-device
        mesh = make_mesh((8,), ("data",))
        with jax.set_mesh(mesh):
            with panel_accounting() as launches:
                out = op.matmul(M)
        assert launches[0].sharded and launches[0].devices == 8
        assert np.array_equal(np.asarray(out), np.asarray(ref))
        print("OK")
        """
        self._run(body)

    @staticmethod
    def _run(body, n=8, timeout=600):
        code = (
            "import os\n"
            f'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"\n'
            + textwrap.dedent(body)
        )
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        proc = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env=env, timeout=timeout,
        )
        assert proc.returncode == 0, (
            f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
        )


class TestEngineAtScale:
    def test_engine_solve_and_cache_n20000(self):
        """A real partitioned engine solve + posterior cache build at
        n=20 000 — the scale smoke the dense modes cannot run — with the
        accounting hook asserting the memory contract throughout."""
        n = 20_000
        X = jax.random.normal(jax.random.PRNGKey(0), (n, 4))
        y = jnp.sin(2 * X[:, 0]) + 0.1 * jax.random.normal(
            jax.random.PRNGKey(1), (n,)
        )
        s = BBMMSettings(num_probes=2, max_cg_iters=10, cg_tol=0.1, precond_rank=0)
        gp = ExactGP(mode="pallas_partitioned", settings=s)
        params = gp.init_params(X)
        params = dict(
            params,
            raw_lengthscale=jnp.float32(np.log(np.expm1(0.25))),
            raw_noise=jnp.float32(np.log(np.expm1(1.0))),
        )
        op = gp.operator(params, X)
        with panel_accounting() as launches:
            with collect() as reports:
                cache = build_posterior_cache(
                    op, y, jax.random.PRNGKey(2), s, variance_cache=False
                )
        assert launches and all(l.panel_rows < l.n for l in launches)
        # the auto-chooser keeps the panel slab within the default budget
        assert all(l.panel_bytes < 140e6 for l in launches)
        assert reports and reports[-1].status == "CONVERGED", reports
        assert bool(jnp.all(jnp.isfinite(cache.alpha)))
        # served mean from the cache is the solve: finite, right shape
        assert cache.alpha.shape == (n,)


class TestPanelFusedCG:
    """Tentpole coverage: ``fuse_cg=True`` on the partitioned path runs the
    PANEL-FUSED step — one fused launch per streamed row-panel per CG
    iteration, the four reductions carried across the panel loop — with NO
    fallback warning and no n×n working set."""

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_engine_matches_unfused_no_fallback(self, backend):
        n = 300
        X, kern = _problem(n)
        op = AddedDiagOperator(
            KernelOperator(
                kernel=kern, X=X, mode="pallas_partitioned", panel_rows=96,
                panel_backend=backend,
            ),
            0.5,
        )
        y = jnp.sin(X[:, 0])
        s = BBMMSettings(num_probes=2, max_cg_iters=40, precond_rank=0, cg_tol=1e-6)
        key = jax.random.PRNGKey(3)
        ref = engine_state(op, y, key, s)
        with warnings.catch_warnings():
            # the fused path is REAL now: any fallback warning fails the test
            warnings.simplefilter("error")
            with panel_accounting() as launches:
                with collect() as reports:
                    st = engine_state(op, y, key, dataclasses.replace(s, fuse_cg=True))
        assert reports[-1].status == "CONVERGED", reports[-1].describe()
        np.testing.assert_allclose(
            np.asarray(st.solve_y), np.asarray(ref.solve_y), rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            float(st.logdet), float(ref.logdet), rtol=1e-4, atol=1e-3
        )
        fused = [lau for lau in launches if lau.fused]
        assert fused, "no fused panel launches recorded"
        for lau in fused:
            assert lau.panel_rows < lau.n  # streamed, never full height
            assert lau.num_panels == -(-lau.n // lau.panel_rows)

    @pytest.mark.parametrize("backend", ["pallas", "xla"])
    def test_tridiag_matches_unfused(self, backend):
        """Same α/β Lanczos coefficients as the unfused loop — the logdet
        estimate rides on these, so they must agree, not just the solves."""
        from repro.core.mbcg import mbcg

        n = 320
        X, kern = _problem(n)
        op = AddedDiagOperator(
            PartitionedKernelOperator(
                kernel=kern, X=X, panel_rows=96, backend=backend
            ),
            0.5,
        )
        step = op.fused_cg_step_fn()
        assert step is not None, "partitioned operator must advertise a fused step"
        B = jax.random.normal(jax.random.PRNGKey(1), (n, 3))
        res_f = mbcg(op.matmul, B, max_iters=10, tol=0.0, fused_step=step)
        res_u = mbcg(op.matmul, B, max_iters=10, tol=0.0)
        np.testing.assert_allclose(
            np.asarray(res_f.solves), np.asarray(res_u.solves), rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(res_f.tridiag_alpha), np.asarray(res_u.tridiag_alpha),
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(res_f.tridiag_beta), np.asarray(res_u.tridiag_beta),
            rtol=1e-4, atol=1e-5,
        )

    def test_one_launch_per_panel_no_dense_aval(self):
        """The perf contract, asserted on the jaxpr: ONE pallas launch per
        row-panel per CG iteration (the scan-rolled panel loop counts once
        per trip), and no (n, n) intermediate anywhere."""
        from benchmarks.fused import count_pallas_launches

        n, p, t = 300, 96, 3
        X, kern = _problem(n)
        op = AddedDiagOperator(
            PartitionedKernelOperator(kernel=kern, X=X, panel_rows=p, backend="pallas"),
            0.5,
        )
        step = op.fused_cg_step_fn()
        B = jax.random.normal(jax.random.PRNGKey(1), (n, t))
        z = jnp.zeros((t,))
        jaxpr = jax.make_jaxpr(lambda s: step(*s))((B, B, B, B, z, z, jnp.ones((t,))))
        num_panels = -(-n // p)
        assert count_pallas_launches(jaxpr) == num_panels

        def all_avals(j):
            j = getattr(j, "jaxpr", j)
            for eqn in j.eqns:
                for v in eqn.outvars:
                    yield v.aval
                for param in eqn.params.values():
                    leaves = param if isinstance(param, (list, tuple)) else [param]
                    for leaf in leaves:
                        if hasattr(leaf, "eqns") or hasattr(leaf, "jaxpr"):
                            yield from all_avals(leaf)

        assert not any(
            getattr(a, "shape", ()) == (n, n) for a in all_avals(jaxpr)
        ), "panel-fused step materialized an n×n intermediate"

    def test_batched_sigma2_declines_with_one_warning(self):
        """Satellite: the unfused fallback warns once per operator, not once
        per solve — repeated step-fn requests on the same operator are
        silent."""
        n = 160
        X, kern = _problem(n)
        op = AddedDiagOperator(
            KernelOperator(
                kernel=kern, X=X, mode="pallas_partitioned", panel_rows=64
            ),
            jnp.full((3,), 0.5),
        )
        with pytest.warns(UserWarning, match="unfused"):
            assert op.fused_cg_step_fn() is None
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            assert op.fused_cg_step_fn() is None  # same operator: no re-warn
        assert not w, [str(x.message) for x in w]
        # a genuinely new operator (fresh arrays) warns afresh
        X2, kern2 = _problem(n, seed=7)
        op2 = AddedDiagOperator(
            KernelOperator(
                kernel=kern2, X=X2, mode="pallas_partitioned", panel_rows=64
            ),
            jnp.full((3,), 0.5),
        )
        with pytest.warns(UserWarning, match="unfused"):
            assert op2.fused_cg_step_fn() is None


class TestShardedFused:
    """Panel-fused CG across 8 forced CPU devices: bitwise 1-vs-N solves
    (deterministic ordered reduction fold) and the band-sharded custom-VJP
    backward (gradient-pass panels re-streamed on all devices; also the fix
    that makes pallas-backend sharded matmuls differentiable at all)."""

    def test_fused_engine_bitwise_1_vs_8_devices(self):
        """The full fused engine batch (y + probes, t=3): solves AND logdet
        bitwise across 1 vs 8 devices on both backends.  t >= 2 matters: at
        t=1 XLA-CPU lowers the per-panel (p × n)·(n × 1) product as a GEMV
        whose in-context vectorization differs between the single-device
        scan body and the shard_map body, so single-RHS fused solves are
        only near-bitwise — the engine never runs t=1 (probes ride along)."""
        body = """
        import warnings
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import (AddedDiagOperator, BBMMSettings,
                                PartitionedKernelOperator, collect, engine_state)
        from repro.gp import RBFKernel

        assert jax.device_count() == 8
        n = 768  # 96-row band per device == panel_rows: one panel per device
        X = jax.random.normal(jax.random.PRNGKey(0), (n, 4))
        kern = RBFKernel(lengthscale=jnp.float32(0.7), outputscale=jnp.float32(1.3))
        y = jnp.sin(X[:, 0])
        key = jax.random.PRNGKey(5)
        s = BBMMSettings(num_probes=2, max_cg_iters=25, precond_rank=0,
                         cg_tol=1e-4, fuse_cg=True)
        mesh = make_mesh((8,), ("data",))
        for backend in ("xla", "pallas"):
            single = AddedDiagOperator(PartitionedKernelOperator(
                kernel=kern, X=X, panel_rows=96, backend=backend,
                data_axes=()), 0.5)
            sharded = AddedDiagOperator(PartitionedKernelOperator(
                kernel=kern, X=X, panel_rows=96, backend=backend,
                mesh=mesh), 0.5)
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                with collect() as r1:
                    st1 = engine_state(single, y, key, s)
                with collect() as r8:
                    st8 = engine_state(sharded, y, key, s)
            assert r1[-1].status == r8[-1].status, (backend, r1[-1], r8[-1])
            assert np.array_equal(np.asarray(st1.solve_y),
                                  np.asarray(st8.solve_y)), (
                backend, float(jnp.max(jnp.abs(st1.solve_y - st8.solve_y))))
            assert float(st1.logdet) == float(st8.logdet), (
                backend, float(st1.logdet), float(st8.logdet))
        print("OK")
        """
        TestSharded._run(body)

    def test_band_sharded_backward_grads(self):
        body = """
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.core import BBMMSettings, PartitionedKernelOperator
        from repro.gp import ExactGP, KernelOperator, RBFKernel

        assert jax.device_count() == 8
        n = 512
        X = jax.random.normal(jax.random.PRNGKey(0), (n, 4))
        M = jax.random.normal(jax.random.PRNGKey(1), (n, 2))
        mesh = make_mesh((8,), ("data",))

        def loss(ell, backend, use_mesh):
            kern = RBFKernel(lengthscale=ell, outputscale=jnp.float32(1.3))
            kw = dict(mesh=mesh) if use_mesh else dict(data_axes=())
            op = PartitionedKernelOperator(
                kernel=kern, X=X, panel_rows=64, backend=backend, **kw)
            return jnp.sum(op.matmul(M) ** 2)

        def loss_dense(ell):
            kern = RBFKernel(lengthscale=ell, outputscale=jnp.float32(1.3))
            return jnp.sum(
                KernelOperator(kernel=kern, X=X, mode="dense").matmul(M) ** 2)

        g_ref = jax.grad(loss_dense)(jnp.float32(0.7))
        for backend in ("xla", "pallas"):
            g8 = jax.grad(loss)(jnp.float32(0.7), backend, True)
            g1 = jax.grad(loss)(jnp.float32(0.7), backend, False)
            np.testing.assert_allclose(float(g8), float(g_ref), rtol=1e-4)
            np.testing.assert_allclose(float(g8), float(g1), rtol=1e-5)

        # RHS cotangent through the sharded custom VJP
        kern = RBFKernel(lengthscale=jnp.float32(0.7), outputscale=jnp.float32(1.3))
        op8 = PartitionedKernelOperator(kernel=kern, X=X, panel_rows=64,
                                        backend="xla", mesh=mesh)
        dense = KernelOperator(kernel=kern, X=X, mode="dense")
        gM8 = jax.grad(lambda m: jnp.sum(op8.matmul(m) ** 2))(M)
        gMd = jax.grad(lambda m: jnp.sum(dense.matmul(m) ** 2))(M)
        np.testing.assert_allclose(np.asarray(gM8), np.asarray(gMd),
                                   rtol=1e-4, atol=1e-4)

        # MLL grads through the band-sharded backward (ambient mesh),
        # unfused and panel-fused solves
        y = jnp.sin(X[:, 0])
        key = jax.random.PRNGKey(2)
        s = BBMMSettings(num_probes=2, max_cg_iters=25, precond_rank=0,
                         panel_rows=64)
        gp = ExactGP(mode="pallas_partitioned", settings=s)
        gp_f = ExactGP(mode="pallas_partitioned",
                       settings=dataclasses.replace(s, fuse_cg=True))
        params = gp.init_params(X)
        lp1, g1 = jax.value_and_grad(gp.loss)(params, X, y, key)
        with jax.set_mesh(mesh):
            lp8, g8 = jax.value_and_grad(gp.loss)(params, X, y, key)
            lpf, gf = jax.value_and_grad(gp_f.loss)(params, X, y, key)
        np.testing.assert_allclose(float(lp8), float(lp1), rtol=1e-4)
        np.testing.assert_allclose(float(lpf), float(lp1), rtol=1e-3)
        for k in params:
            np.testing.assert_allclose(np.asarray(g8[k]), np.asarray(g1[k]),
                                       rtol=2e-3, atol=1e-4)
            np.testing.assert_allclose(np.asarray(gf[k]), np.asarray(g1[k]),
                                       rtol=5e-3, atol=5e-4)
        print("OK")
        """
        TestSharded._run(body)


class TestDenseDirectRouting:
    def test_small_n_routes_to_cholesky(self):
        n = 96
        X, kern = _problem(n)
        op = AddedDiagOperator(
            DenseOperator(kern(X, X)), 0.5
        )
        y = jnp.sin(X[:, 0])
        s = BBMMSettings(
            num_probes=2, max_cg_iters=30, precond_rank=0, dense_direct_max_n=128
        )
        with collect() as reports:
            x = solve(op, y, s)
        rep = reports[-1]
        assert rep.rungs and rep.rungs[0].rung == "dense_direct"
        assert rep.status == "CONVERGED" and rep.num_iters == 0
        # the routed answer IS the Cholesky solve
        ref = jnp.linalg.solve(kern(X, X) + 0.5 * jnp.eye(n), y)
        np.testing.assert_allclose(np.asarray(x), np.asarray(ref), rtol=1e-3, atol=1e-4)

    def test_above_threshold_runs_engine(self):
        n = 200
        X, kern = _problem(n)
        op = AddedDiagOperator(DenseOperator(kern(X, X)), 0.5)
        y = jnp.sin(X[:, 0])
        s = BBMMSettings(
            num_probes=2, max_cg_iters=60, precond_rank=0, dense_direct_max_n=128
        )
        with collect() as reports:
            with warnings.catch_warnings():
                warnings.simplefilter("ignore")
                solve(op, y, s)
        rep = reports[-1]
        assert not (rep.rungs and rep.rungs[0].rung == "dense_direct")

    def test_default_off(self):
        assert BBMMSettings().dense_direct_max_n == 0


class TestPanelFaultInjection:
    """Chaos hookup: NaN into a SINGLE panel of a partitioned solve — the
    ladder must heal it without other panels' rows being poisoned."""

    def _op(self, n, X, kern, schedule):
        base = KernelOperator(
            kernel=kern, X=X, mode="pallas_partitioned", panel_rows=64
        )
        return AddedDiagOperator(
            FaultInjectingOperator(base.prepare(), schedule=schedule), 0.5
        )

    def test_fault_confined_to_panel(self):
        n = 256
        X, kern = _problem(n)
        sched = FaultSchedule(nan_calls={0}, panel=(64, 64))
        op = self._op(n, X, kern, sched)
        out = op.matmul(jnp.ones((n, 1)))
        bad = np.asarray(out)[64:128]
        good = np.concatenate([np.asarray(out)[:64], np.asarray(out)[128:]])
        assert np.isnan(bad).all()
        assert np.isfinite(good).all(), "fault leaked outside its panel"

    def test_ladder_heals_single_panel_fault(self):
        n = 256
        X, kern = _problem(n)
        sched = FaultSchedule(nan_calls={0}, panel=(64, 64))
        op = self._op(n, X, kern, sched)
        y = jnp.sin(X[:, 0])
        s = BBMMSettings(
            num_probes=2, max_cg_iters=40, precond_rank=0, cg_tol=1e-3,
            on_failure="degrade",
        )
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with collect() as reports:
                x = solve(op, y, s)
        rep = reports[-1]
        assert rep.status == "CONVERGED", rep.describe()
        assert any(r.rung != "initial" for r in rep.rungs), rep.rungs
        assert any("healed" in str(x.message) for x in w)
        # healed answer matches the clean partitioned solve
        clean = AddedDiagOperator(
            KernelOperator(
                kernel=kern, X=X, mode="pallas_partitioned", panel_rows=64
            ),
            0.5,
        )
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            ref = solve(clean, y, s)
        # the healed solve ran on a later rung (extended CG budget), so it
        # agrees with the clean initial-rung solve only to CG tolerance
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(ref), rtol=1e-2, atol=5e-3
        )
        assert sched.injected, "no fault was actually delivered"

    def test_fused_fault_confined_to_panel(self):
        """Chaos on the PANEL-FUSED step: poisoning one panel mid-iteration
        hits only that panel's rows of V — the other bands' state stays
        finite — while the carried (4, t) reductions go NaN (that is the
        signal the ladder sees)."""
        n = 256
        X, kern = _problem(n)
        sched = FaultSchedule(nan_calls={0}, panel=(64, 64))
        op = self._op(n, X, kern, sched)
        step = op.fused_cg_step_fn()
        assert step is not None, "fault wrapper must forward the fused step"
        t = 2
        B = jax.random.normal(jax.random.PRNGKey(1), (n, t))
        z = jnp.zeros((t,))
        Un, Rn, Dn, Vn, red = step(B, B, B, B, z, z, jnp.ones((t,)))
        V = np.asarray(Vn)
        assert np.isnan(V[64:128]).all()
        assert np.isfinite(V[:64]).all() and np.isfinite(V[128:]).all(), (
            "fused fault leaked outside its panel"
        )
        for arr in (Un, Rn, Dn):
            assert np.isfinite(np.asarray(arr)).all()
        assert all(np.isnan(np.asarray(r)).all() for r in red), (
            "carried reductions must carry the poison to the α/β recurrence"
        )
        assert sched.injected

    def test_ladder_heals_fused_panel_fault(self):
        """A transient NaN inside the fused panel loop ends the fused attempt
        unhealthy; the PR 6 ladder retries (the unfused rung drops fuse_cg)
        and heals to the clean answer."""
        n = 256
        X, kern = _problem(n)
        sched = FaultSchedule(nan_calls={0, 1}, panel=(64, 64))
        op = self._op(n, X, kern, sched)
        y = jnp.sin(X[:, 0])
        s = BBMMSettings(
            num_probes=2, max_cg_iters=40, precond_rank=0, cg_tol=1e-3,
            on_failure="degrade", fuse_cg=True,
        )
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            with collect() as reports:
                x = solve(op, y, s)
        rep = reports[-1]
        assert rep.status == "CONVERGED", rep.describe()
        assert any(r.rung != "initial" for r in rep.rungs), rep.rungs
        assert any("healed" in str(x.message) for x in w)
        clean = AddedDiagOperator(
            KernelOperator(
                kernel=kern, X=X, mode="pallas_partitioned", panel_rows=64
            ),
            0.5,
        )
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            ref = solve(clean, y, dataclasses.replace(s, fuse_cg=False))
        np.testing.assert_allclose(
            np.asarray(x), np.asarray(ref), rtol=1e-2, atol=5e-3
        )
        assert sched.injected, "no fault was actually delivered"
