"""Deep-kernel KISS-GP (``SKI(features=FeatureMap(...))``) against the plain
reference ``bench/reference_dkl_ski.py``, and the interpolated operator's
structured diagonal and rows against the dense W K Wᵀ.

Sizes are cut for the CPU: n = 300 rows of d = 18, the feature net
18-32-16-2 (the benchmark's is 18-1000-500-50-2), a 24 x 24 grid."""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import data, reference_dkl_ski as ref  # noqa: E402
from bench.drivers.train import ADAM_B1, make_step  # noqa: E402
from bench.drivers.train_dkl_ski import flat, nested  # noqa: E402
from repro.core import BBMMSettings, InterpolatedOperator, KroneckerOperator, ToeplitzOperator  # noqa: E402
from repro.gp import SKI, FeatureMap, Grid  # noqa: E402
from repro.gp import dkl  # noqa: E402

N, D, GRID, WIDTHS, BOUNDS = 300, 18, 24, (32, 16, 2), (-1.0, 1.0)
# Enough probes and CG iterations that the program's estimators, not its
# solver, set the gap: a rank-100 preconditioner makes the SLQ log-det of a
# 300-row SKI operator exact to ~1e-7 nats per row, and the trace term of
# the gradient keeps the Hutchinson noise of 1024 probes.
SETTINGS = BBMMSettings(num_probes=1024, precond_rank=100, max_cg_iters=100, cg_tol=1e-6)
LR = 0.01

# Loss: measured gap 9e-8 nats per row (seeds 0-3); 1e-5 is a hundred times
# that, and a bf16 network moves the loss by more.
LOSS_TOL = 1e-5
# Gradient, per leaf, |g_prog - g_ref| / |g_ref|: the trace term's probe
# noise falls as 1/sqrt(probes) (measured worst leaf 0.79 at 10 probes,
# 0.08-0.14 at 512, 0.04-0.08 at 2048); 0.25 holds every leaf at 1024
# probes with room, and a dropped gradient reads 1.
GRAD_TOL = 0.25


def inputs(seed=5):
    X, y = data.regression(N, D, seed)
    X = 2.0 * (X - X.min(0)) / (X.max(0) - X.min(0)) - 1.0
    return jnp.asarray(X, jnp.float32), jnp.asarray(y)


@pytest.fixture(scope="module")
def problem():
    X, y = inputs()
    start = ref.init_params(D, WIDTHS, 5)
    with jax.default_matmul_precision("highest"):
        inv = ref.Inverse(X, y, {k: jnp.asarray(v) for k, v in start.items()},
                          bounds=BOUNDS, size=GRID)
        out = {"X": X, "y": y, "start": start, "loss": inv.loss(),
               "grad": {k: np.asarray(v) for k, v in inv.grad().items()},
               "residual": inv.residual()}
    inv.free()
    return out


def program_step(problem, model):
    """One ``fit_gp`` step (value_and_grad(model.loss), then Adam) from the
    reference's start: (loss, gradient by flat leaf name)."""
    init, step = make_step(model, LR)
    params = nested(problem["start"])
    _, opt, loss = step(params, init(params), problem["X"], problem["y"],
                        jax.random.PRNGKey(0))
    return float(loss), {k: v / (1.0 - ADAM_B1) for k, v in flat(opt.mu).items()}


def gaps(problem, loss, grad):
    g_ref = problem["grad"]
    rel = {k: float(np.linalg.norm(grad[k] - g_ref[k]) / np.linalg.norm(g_ref[k]))
           for k in g_ref}
    return abs(loss - problem["loss"]) / N, rel


def test_reference_solves_its_own_system(problem):
    assert problem["residual"] < 1e-4


def test_reference_matches_textbook_cholesky(problem):
    """The reference's block-sweep loss and VJP gradient against -MLL by a
    dense Cholesky, differentiated by jax.grad: both exact, so the gap is
    f32 round-off (measured: loss 1e-7 relative, gradient leaves <= 2.6e-4)."""
    X, y = problem["X"], problem["y"]

    def textbook(p):
        K = ref.khat(p, X, bounds=BOUNDS, size=GRID)
        L = jnp.linalg.cholesky(K)
        alpha = jax.scipy.linalg.cho_solve((L, True), y)
        return 0.5 * (y @ alpha + 2 * jnp.sum(jnp.log(jnp.diag(L))) + N * math.log(2 * math.pi))

    with jax.default_matmul_precision("highest"):
        loss, grad = jax.value_and_grad(textbook)(
            {k: jnp.asarray(v) for k, v in problem["start"].items()})
    assert abs(float(loss) - problem["loss"]) / abs(problem["loss"]) < 1e-5
    for k, g in grad.items():
        err = np.linalg.norm(problem["grad"][k] - np.asarray(g)) / np.linalg.norm(g)
        assert err < 2e-3, (k, err)


def test_program_operator_is_the_reference_khat(problem):
    """The program's W(z̃) K_UU Wᵀ + σ²I, densified, is the reference's K̂."""
    model = SKI(grid_size=GRID, features=FeatureMap(widths=WIDTHS), settings=SETTINGS)
    params = nested(problem["start"])
    with jax.default_matmul_precision("highest"):
        dense = model.operator(params, problem["X"]).to_dense()
        want = ref.khat({k: jnp.asarray(v) for k, v in problem["start"].items()},
                        problem["X"], bounds=BOUNDS, size=GRID)
    np.testing.assert_allclose(dense, want, atol=5e-6)


def _drop_feature_gradient(monkeypatch):
    real = FeatureMap.__call__
    monkeypatch.setattr(FeatureMap, "__call__",
                        lambda self, net, X: jax.lax.stop_gradient(real(self, net, X)))


def _bf16_network(monkeypatch):
    real = dkl.mlp_apply

    def bf16(net, X, act=jax.nn.tanh):
        cast = jax.tree.map(lambda a: a.astype(jnp.bfloat16), net)
        return real(cast, X.astype(jnp.bfloat16), act).astype(jnp.float32)

    monkeypatch.setattr(dkl, "mlp_apply", bf16)


@pytest.mark.parametrize("fault", [None, _drop_feature_gradient, _bf16_network],
                         ids=["sound", "features-gradient-dropped", "network-in-bf16"])
def test_program_step_against_reference(problem, monkeypatch, fault):
    """The program's -MLL and gradient over every leaf, network included,
    through the fit_gp step: sound within the tolerances, each negative
    control outside at least one of them."""
    if fault is not None:
        fault(monkeypatch)
    model = SKI(grid_size=GRID, features=FeatureMap(widths=WIDTHS), settings=SETTINGS)
    loss_gap, rel = gaps(problem, *program_step(problem, model))
    ok = loss_gap < LOSS_TOL and max(rel.values()) < GRAD_TOL
    assert ok == (fault is None), (loss_gap, rel)


@pytest.mark.parametrize("dims", [1, 2])
def test_structured_diagonal_and_rows_match_dense(dims):
    """diagonal() from K_UU's entries over the grid's shared stencil and
    row(i) from one base product equal the dense W K Wᵀ, on a Toeplitz
    (1-D) and a Kronecker-of-Toeplitz (2-D) grid; the Kronecker entries
    equal the dense Kronecker product's."""
    x = jax.random.uniform(jax.random.PRNGKey(dims), (40, dims))
    sizes = (20, 12)[:dims]
    grid = Grid.fit(x, sizes)
    idx, val = grid.interpolate(x)
    cols = [jnp.exp(-0.5 * ((grid.points(k) - grid.points(k)[0]) / (0.3 + 0.1 * k)) ** 2)
            for k in range(dims)]
    base = (ToeplitzOperator(cols[0]) if dims == 1
            else KroneckerOperator(tuple(ToeplitzOperator(c) for c in cols)))
    op = InterpolatedOperator(indices=idx, values=val, base=base)
    K = ToeplitzOperator(cols[0]).to_dense()
    for c in cols[1:]:
        K = jnp.kron(K, ToeplitzOperator(c).to_dense())
    m = K.shape[0]
    W = jnp.zeros((40, m)).at[jnp.arange(40)[:, None], idx].add(val)
    dense = W @ K @ W.T
    a = jnp.arange(m)
    np.testing.assert_allclose(base.entries(a[:, None], a[None, :]), K, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(op.diagonal(), jnp.diagonal(dense), rtol=1e-5, atol=1e-6)
    for i in (0, 17, 39):
        np.testing.assert_allclose(op.row(i), dense[i], rtol=1e-5, atol=1e-6)


def test_static_grid_is_gpytorchs_extended_grid():
    """Grid.over_bounds: size points from lo - e to hi + e, e = (hi-lo)/(size-2),
    as the reference places them."""
    g = Grid.over_bounds((-1.0, 1.0), (100, 100))
    first, step = ref.grid_axis((-1.0, 1.0), 100)
    # f32 points against float64 ones: a few ulps of the grid's unit scale
    np.testing.assert_allclose(g.points(0), first + step * np.arange(100), rtol=0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g.points(1))[[0, -1]], [-1 - 2 / 98, 1 + 2 / 98], rtol=0, atol=1e-6)


def test_feature_ski_trains_and_predicts_through_the_protocol():
    """fit_gp, then predict, on the feature map's grid: the loss falls and
    the mean at training points follows y."""
    X, y = inputs(7)
    model = SKI(grid_size=GRID, features=FeatureMap(widths=WIDTHS),
                settings=BBMMSettings(max_cg_iters=40))
    params, hist = model.fit(X, y, steps=30, lr=LR)
    assert hist[-1] < hist[0]
    mean, var = model.predict(params, model.prepare_inputs(X), y, X[:40])
    assert bool(jnp.all(var > 0))
    assert float(jnp.corrcoef(mean, y[:40])[0, 1]) > 0.5
