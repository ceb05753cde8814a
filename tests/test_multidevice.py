"""Multi-device behaviour on 8 fake CPU devices.

XLA locks the device count at first init, so each scenario runs in a
subprocess with XLA_FLAGS set — the same mechanism launch/dryrun.py uses
for the 512-device production mesh.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(body: str, n: int = 8, timeout=600):
    code = (
        "import os\n"
        f'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"\n'
        + textwrap.dedent(body)
    )
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=timeout,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr}"
    return proc.stdout


class TestShardedGP:
    def test_sharded_kernel_operator_matches_dense(self):
        run_with_devices(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from jax.sharding import PartitionSpec as P
            from repro.core import ShardedKernelOperator
            from repro.gp import KernelOperator, RBFKernel

            mesh = make_mesh((4, 2), ("data", "model"))
            kern = RBFKernel(lengthscale=jnp.float32(0.5), outputscale=jnp.float32(1.2))
            X = jax.random.normal(jax.random.PRNGKey(0), (64, 3))
            M = jax.random.normal(jax.random.PRNGKey(1), (64, 5))
            with jax.set_mesh(mesh):
                op = ShardedKernelOperator(kernel=kern, X=X, data_axes=("data",), chunk=16)
                out = jax.jit(op.matmul)(M)
            ref = KernelOperator(kernel=kern, X=X, mode="dense").matmul(M)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-4, atol=1e-4)
            print("OK")
            """
        )

    def test_distributed_mll_grad_matches_single_device(self):
        run_with_devices(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from jax.sharding import PartitionSpec as P
            from repro.core import (AddedDiagOperator, BBMMSettings,
                                    ShardedKernelOperator, marginal_log_likelihood)
            from repro.gp import KernelOperator, RBFKernel

            X = jax.random.normal(jax.random.PRNGKey(0), (64, 3))
            y = jnp.sin(X @ jnp.ones(3))
            key = jax.random.PRNGKey(1)
            s = BBMMSettings(num_probes=8, max_cg_iters=64, precond_rank=0, cg_tol=1e-9)

            def mll_dense(ell):
                kern = RBFKernel(lengthscale=ell, outputscale=jnp.float32(1.0))
                op = AddedDiagOperator(KernelOperator(kernel=kern, X=X, mode="dense"), 0.1)
                return marginal_log_likelihood(op, y, key, s)

            g_dense = jax.grad(mll_dense)(jnp.float32(0.7))

            mesh = make_mesh((8,), ("data",))
            with jax.set_mesh(mesh):
                def mll_shard(ell):
                    kern = RBFKernel(lengthscale=ell, outputscale=jnp.float32(1.0))
                    op = AddedDiagOperator(
                        ShardedKernelOperator(kernel=kern, X=X, data_axes=("data",), chunk=16), 0.1)
                    return marginal_log_likelihood(op, y, key, s)
                g_shard = jax.jit(jax.grad(mll_shard))(jnp.float32(0.7))
            np.testing.assert_allclose(float(g_shard), float(g_dense), rtol=2e-3)
            print("OK")
            """
        )

    def test_sharded_pallas_matmul_matches_single_device(self):
        """Acceptance: the shard_map row-partitioned Pallas path ≡ the
        single-device Pallas path on a multi-shard CPU mesh."""
        run_with_devices(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.gp import KernelOperator, RBFKernel, MaternKernel
            from repro.kernels.kernel_matmul.ops import (
                fused_kernel_matmul, sharded_kernel_matmul)

            assert jax.device_count() == 8
            mesh = make_mesh((8,), ("data",))
            X = jax.random.normal(jax.random.PRNGKey(0), (96, 3))
            M = jax.random.normal(jax.random.PRNGKey(1), (96, 5))
            for kern in [
                RBFKernel(lengthscale=jnp.float32(0.5), outputscale=jnp.float32(1.2)),
                RBFKernel(lengthscale=jnp.array([0.3, 0.8, 1.5]),  # ARD
                          outputscale=jnp.float32(0.9)),
                MaternKernel(lengthscale=jnp.float32(0.7), outputscale=jnp.float32(1.0), nu=2.5),
            ]:
                ref = fused_kernel_matmul(X, M, kern.lengthscale, kern.outputscale,
                                          jnp.float32(0.0),
                                          kernel_type="rbf" if isinstance(kern, RBFKernel) else "matern52")
                out = sharded_kernel_matmul(kern, X, M, mesh, ("data",))
                np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                           rtol=1e-5, atol=1e-5)
                # operator-facing path, jitted, mesh from context
                with jax.set_mesh(mesh):
                    op = KernelOperator(kernel=kern, X=X, mode="pallas_sharded")
                    out2 = jax.jit(op.matmul)(M)
                np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                                           rtol=1e-5, atol=1e-5)
            print("OK")
            """
        )

    def test_sharded_pivoted_cholesky_matches_replicated(self):
        """ISSUE 3: the shard_map row-sharded pivoted-Cholesky build (elected
        global pivots, psum'd pivot rows) ≡ the replicated build, standalone
        AND auto-wired through build_preconditioner into the full engine."""
        run_with_devices(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.core import (AddedDiagOperator, BBMMSettings, DenseOperator,
                                    build_preconditioner, marginal_log_likelihood,
                                    pivoted_cholesky_dense, pivoted_cholesky_sharded)
            from repro.gp import KernelOperator, RBFKernel

            mesh = make_mesh((8,), ("data",))
            kern = RBFKernel(lengthscale=jnp.float32(0.5), outputscale=jnp.float32(1.2))
            X = jax.random.normal(jax.random.PRNGKey(0), (96, 3))
            K = kern(X, X)
            L_ref = pivoted_cholesky_dense(K, 6)
            with jax.set_mesh(mesh):
                L_sh = pivoted_cholesky_sharded(DenseOperator(K), 6)
            np.testing.assert_allclose(np.asarray(L_sh), np.asarray(L_ref), atol=1e-5)

            # auto-wiring: a live mesh row-shards the generic preconditioner
            # path inside jit, and the full engine agrees with replicated
            op = AddedDiagOperator(KernelOperator(kernel=kern, X=X, mode="dense"), 0.1)
            y = jnp.sin(X @ jnp.ones(3))
            s = BBMMSettings(num_probes=8, max_cg_iters=64, precond_rank=5, cg_tol=1e-9)
            with jax.set_mesh(mesh):
                P = jax.jit(lambda: build_preconditioner(op, 5))()
                # same row access, replicated build: the sharding must be
                # numerically invisible (dense-K references are fragile here:
                # the RBF diagonal is constant, so pivot TIES make the
                # elimination order fp-sensitive between row accessors)
                P_rep = jax.jit(lambda: build_preconditioner(op, 5, shard=False))()
                mll_sh = float(marginal_log_likelihood(op, y, jax.random.PRNGKey(1), s))
            np.testing.assert_allclose(
                np.asarray(P.L), np.asarray(P_rep.L), atol=1e-5)
            mll_rep = float(marginal_log_likelihood(op, y, jax.random.PRNGKey(1), s))
            np.testing.assert_allclose(mll_sh, mll_rep, rtol=1e-4)

            # indivisible n falls back to the replicated build (no error)
            X2 = jax.random.normal(jax.random.PRNGKey(2), (97, 3))
            op2 = AddedDiagOperator(KernelOperator(kernel=kern, X=X2, mode="dense"), 0.1)
            with jax.set_mesh(mesh):
                P2 = build_preconditioner(op2, 4)
            assert P2.L.shape == (97, 4)
            print("OK")
            """
        )

    def test_sharded_pallas_mll_end_to_end(self):
        """Full engine (MLL value) through the sharded Pallas operator."""
        run_with_devices(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.core import AddedDiagOperator, BBMMSettings, marginal_log_likelihood
            from repro.gp import KernelOperator, RBFKernel

            mesh = make_mesh((4,), ("data",))
            X = jax.random.normal(jax.random.PRNGKey(0), (64, 3))
            y = jnp.sin(X @ jnp.ones(3))
            key = jax.random.PRNGKey(1)
            s = BBMMSettings(num_probes=8, max_cg_iters=64, precond_rank=0, cg_tol=1e-9)
            kern = RBFKernel(lengthscale=jnp.float32(0.7), outputscale=jnp.float32(1.0))

            mll_dense = marginal_log_likelihood(
                AddedDiagOperator(KernelOperator(kernel=kern, X=X, mode="dense"), 0.1),
                y, key, s)
            with jax.set_mesh(mesh):
                op = AddedDiagOperator(
                    KernelOperator(kernel=kern, X=X, mode="pallas_sharded"), 0.1)
                mll_shard = marginal_log_likelihood(op, y, key, s)
            np.testing.assert_allclose(float(mll_shard), float(mll_dense), rtol=1e-4)
            print("OK")
            """
        )


class TestTrainStepSharded:
    def test_llama_reduced_train_step_on_mesh(self):
        """The dry-run machinery end-to-end on a 4x2 mesh with REAL arrays."""
        run_with_devices(
            """
            import jax, jax.numpy as jnp
            from repro.launch.mesh import make_mesh
            from repro.configs import get_config
            from repro.distributed.sharding import params_shardings, named_shardings
            from repro.models import build_model, make_train_step

            cfg = get_config("llama3.2-1b").reduced(num_heads=4, num_kv_heads=2, vocab_size=512)
            bundle = build_model(cfg)
            mesh = make_mesh((4, 2), ("data", "model"))
            with jax.set_mesh(mesh):
                params = bundle.init(jax.random.PRNGKey(0))
                specs = params_shardings(params, bundle.stacked_paths)
                params = jax.tree.map(
                    lambda x, s: jax.device_put(x, jax.sharding.NamedSharding(mesh, s)),
                    params, specs,
                    is_leaf=lambda x: hasattr(x, "shape"),
                )
                step, init_opt = make_train_step(bundle, lr=1e-3)
                opt = init_opt(params)
                batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, 512)}
                p2, o2, m = jax.jit(step)(params, opt, batch)
                loss = float(m["loss"])
                assert 0 < loss < 20, loss
            print("OK", loss)
            """
        )

    def test_moe_ep_sharded(self):
        run_with_devices(
            """
            import jax, jax.numpy as jnp
            from repro.launch.mesh import make_mesh
            from repro.configs import get_config
            from repro.models import build_model, make_train_step

            cfg = get_config("granite-moe-1b-a400m").reduced(num_experts=4, top_k=2, vocab_size=512)
            bundle = build_model(cfg)
            mesh = make_mesh((2, 4), ("data", "model"))
            with jax.set_mesh(mesh):
                params = bundle.init(jax.random.PRNGKey(0))
                step, init_opt = make_train_step(bundle, lr=1e-3)
                opt = init_opt(params)
                batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, 512)}
                p2, o2, m = jax.jit(step)(params, opt, batch)
                assert 0 < float(m["loss"]) < 20
            print("OK")
            """
        )


class TestPipelineParallel:
    def test_gpipe_matches_sequential(self):
        run_with_devices(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.distributed.pipeline import pipeline_forward

            S, M, mb, d = 4, 8, 4, 16
            mesh = make_mesh((S,), ("stage",))
            ws = jax.random.normal(jax.random.PRNGKey(0), (S, d, d)) * 0.3

            def stage_fn(w, x):
                return jnp.tanh(x @ w)

            x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, d))
            out = pipeline_forward(stage_fn, ws, x, mesh=mesh)

            ref = x
            for i in range(S):
                ref = jnp.tanh(ref @ ws[i])
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)
            print("OK")
            """
        )


class TestElasticRestore:
    def test_checkpoint_reshards_across_mesh_sizes(self):
        run_with_devices(
            """
            import tempfile, jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from jax.sharding import PartitionSpec as P, NamedSharding
            from repro.checkpoint.checkpointer import Checkpointer

            tree = {"w": jnp.arange(64.0).reshape(8, 8)}
            with tempfile.TemporaryDirectory() as d:
                ck = Checkpointer(d)
                # save from an 8-way sharded layout
                mesh8 = make_mesh((8,), ("data",))
                sharded = jax.device_put(tree["w"], NamedSharding(mesh8, P("data", None)))
                ck.save(0, {"w": sharded})
                # restore onto a 2-way mesh (elastic downsize)
                mesh2 = make_mesh((2, 4), ("data", "model"))
                target = {"w": NamedSharding(mesh2, P("model", "data"))}
                out = ck.restore(0, tree, shardings=target)
                np.testing.assert_allclose(np.asarray(out["w"]), np.asarray(tree["w"]))
                assert out["w"].sharding.spec == P("model", "data")
            print("OK")
            """
        )


class TestBf16Tiles:
    def test_pallas_sharded_mixed_and_batched(self):
        """pallas_sharded with compute_dtype='bfloat16' (half-width gather
        payload) stays within CG-recoverable distance of f32, and a batched
        (b, n, t) RHS flows through the native batch grid per shard."""
        run_with_devices(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.gp import KernelOperator, RBFKernel

            mesh = make_mesh((8,), ("data",))
            kern = RBFKernel(lengthscale=jnp.float32(0.5), outputscale=jnp.float32(1.0))
            X = jax.random.normal(jax.random.PRNGKey(0), (64, 3))
            M = jax.random.normal(jax.random.PRNGKey(1), (64, 4))
            Mb = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 4))
            ref = KernelOperator(kernel=kern, X=X, mode="dense").matmul(M)
            ref_b = KernelOperator(kernel=kern, X=X, mode="dense").matmul(Mb)
            with jax.set_mesh(mesh):
                op = KernelOperator(kernel=kern, X=X, mode="pallas_sharded")
                o16 = op.with_compute_dtype("mixed").matmul(M)
                rel = float(jnp.linalg.norm(o16 - ref) / jnp.linalg.norm(ref))
                assert rel < 0.02, rel
                ob = op.matmul(Mb)  # batched f32 through the sharded path
            assert ob.shape == (2, 64, 4)
            np.testing.assert_allclose(np.asarray(ob), np.asarray(ref_b),
                                       rtol=5e-4, atol=5e-4)
            print("OK", rel)
            """
        )

    def test_bf16_sharded_operator_close_to_f32(self):
        """§Perf hillclimb 3: bf16 tiles must stay within CG-recoverable
        distance of the f32 operator."""
        run_with_devices(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.core import ShardedKernelOperator
            from repro.gp import RBFKernel

            mesh = make_mesh((8,), ("data",))
            kern = RBFKernel(lengthscale=jnp.float32(0.5), outputscale=jnp.float32(1.0))
            X = jax.random.normal(jax.random.PRNGKey(0), (64, 3))
            M = jax.random.normal(jax.random.PRNGKey(1), (64, 4))
            with jax.set_mesh(mesh):
                f32 = ShardedKernelOperator(kernel=kern, X=X, data_axes=("data",), chunk=16)
                b16 = ShardedKernelOperator(kernel=kern, X=X, data_axes=("data",), chunk=16,
                                            compute_dtype="bfloat16")
                o32 = jax.jit(f32.matmul)(M)
                o16 = jax.jit(b16.matmul)(M)
            rel = float(jnp.linalg.norm(o16 - o32) / jnp.linalg.norm(o32))
            assert rel < 0.02, rel  # bf16 tile rounding, CG self-corrects
            print("OK", rel)
            """
        )


@pytest.mark.fused
class TestFusedCGSharded:
    """Fused CG step under shard_map (ISSUE 4): per-device fused row-band
    execution with psum'd reductions must match the replicated reference."""

    def test_sharded_fused_step_and_engine(self):
        run_with_devices(
            """
            import dataclasses
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.core import AddedDiagOperator, BBMMSettings, engine_state, mbcg
            from repro.core.mbcg import xla_cg_step
            from repro.gp import KernelOperator, RBFKernel

            mesh = make_mesh((4, 2), ("data", "model"))
            kern = RBFKernel(lengthscale=jnp.float32(0.5), outputscale=jnp.float32(1.2))
            X = jax.random.normal(jax.random.PRNGKey(0), (64, 3))
            y = jnp.sin(X @ jnp.ones(3))
            with jax.set_mesh(mesh):
                op = AddedDiagOperator(
                    KernelOperator(kernel=kern, X=X, mode="pallas_sharded",
                                   data_axes=("data",)), 0.1)
                prepared = op.prepare()
                step = prepared.fused_cg_step_fn()
                assert step is not None
                # single fused step parity (incl. psum'd reductions)
                ref = xla_cg_step(prepared.matmul)
                ks = jax.random.split(jax.random.PRNGKey(3), 6)
                U, R, D, V = (jax.random.normal(k, (64, 5)) for k in ks[:4])
                al = jax.random.normal(ks[4], (5,))
                be = jax.random.normal(ks[5], (5,)) * 0.3
                ga = jnp.ones((5,))
                out_s, out_r = step(U, R, D, V, al, be, ga), ref(U, R, D, V, al, be, ga)
                for a, b in zip(out_s[:4], out_r[:4]):
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               rtol=2e-4, atol=2e-4)
                for a, b in zip(out_s[4], out_r[4]):
                    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                               rtol=2e-4, atol=2e-3)
                # engine-level: fused == unfused on the sharded operator,
                # batched RHS included (native batch grid composes)
                s0 = BBMMSettings(num_probes=6, max_cg_iters=48,
                                  precond_rank=0, cg_tol=1e-6)
                sf = dataclasses.replace(s0, fuse_cg=True)
                st_u = engine_state(op, y, jax.random.PRNGKey(7), s0)
                st_f = engine_state(op, y, jax.random.PRNGKey(7), sf)
                np.testing.assert_allclose(np.asarray(st_f.solve_y),
                                           np.asarray(st_u.solve_y),
                                           rtol=1e-3, atol=1e-4)
                B = jnp.stack([jnp.stack([y, -y], -1), jnp.stack([2*y, y*y], -1)])
                rf = mbcg(prepared.matmul, B, max_iters=48, tol=1e-6, fused_step=step)
                ru = mbcg(prepared.matmul, B, max_iters=48, tol=1e-6)
                np.testing.assert_allclose(np.asarray(rf.solves), np.asarray(ru.solves),
                                           rtol=1e-3, atol=1e-4)
            print("OK")
            """
        )


@pytest.mark.multitask
class TestMultitaskSharded:
    """Kronecker multitask covariance with a ROW-SHARDED data kernel
    (ISSUE 5): the O(n²) data matmul inside the Kronecker MVM runs the
    shard_map'd Pallas path, so the T·t-column block is computed across
    the mesh with one RHS all-gather — parity with the replicated dense
    operator, engine solve included."""

    def test_kronecker_sharded_data_kernel(self):
        run_with_devices(
            """
            import jax, jax.numpy as jnp, numpy as np
            from repro.launch.mesh import make_mesh
            from repro.core import (
                BBMMSettings,
                KroneckerAddedDiagOperator,
                KroneckerKernelOperator,
                solve,
            )
            from repro.gp import KernelOperator, RBFKernel

            mesh = make_mesh((8,), ("data",))
            kern = RBFKernel(lengthscale=jnp.float32(0.5),
                             outputscale=jnp.float32(1.1))
            T, n = 4, 64
            X = jax.random.normal(jax.random.PRNGKey(0), (n, 3))
            Bt = 0.4 * jax.random.normal(jax.random.PRNGKey(1), (T, 2))
            KT = Bt @ Bt.T + jnp.eye(T)
            noise = 0.1 + 0.1 * jnp.arange(T)
            M = jax.random.normal(jax.random.PRNGKey(2), (n * T, 5))

            def multitask_op(mode):
                return KroneckerAddedDiagOperator(
                    KroneckerKernelOperator(
                        KernelOperator(kernel=kern, X=X, mode=mode), KT
                    ),
                    noise,
                )

            ref_op = multitask_op("dense")
            ref = ref_op.matmul(M)
            with jax.set_mesh(mesh):
                op = multitask_op("pallas_sharded")
                out = op.matmul(M)
                # prepare() recurses into the sharded data kernel: the CG
                # loop's per-iteration matmul reuses the pre-scaled X
                out_p = op.prepare().matmul(M)
                np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                           rtol=5e-4, atol=5e-4)
                np.testing.assert_allclose(np.asarray(out_p), np.asarray(ref),
                                           rtol=5e-4, atol=5e-4)
                # engine solve through the sharded Kronecker operator
                s = BBMMSettings(num_probes=4, max_cg_iters=60,
                                 cg_tol=1e-6, precond_rank=0)
                y = jnp.sin(X @ jnp.ones(3))
                yl = jnp.tile(y[:, None], (1, T)).reshape(-1)
                sol = solve(op, yl[:, None], s)
                sol_ref = solve(ref_op, yl[:, None], s)
                np.testing.assert_allclose(np.asarray(sol), np.asarray(sol_ref),
                                           rtol=1e-3, atol=1e-3)
            print("OK")
            """
        )
