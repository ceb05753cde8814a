"""Pallas fused kernel matmul vs jnp oracle — shape/dtype/kernel sweep."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.kernel_matmul.ops import fused_kernel_matmul
from repro.kernels.kernel_matmul.ref import kernel_matmul_ref


@pytest.mark.parametrize("kernel_type", ["rbf", "matern12", "matern32", "matern52"])
@pytest.mark.parametrize("n,d,t", [(256, 4, 8), (300, 7, 11), (512, 16, 64)])
def test_matches_ref(kernel_type, n, d, t):
    kx, km = jax.random.split(jax.random.PRNGKey(hash((kernel_type, n)) % 2**31))
    X = jax.random.normal(kx, (n, d))
    M = jax.random.normal(km, (n, t))
    out = fused_kernel_matmul(
        X, M, jnp.float32(0.7), jnp.float32(1.3), jnp.float32(0.05),
        kernel_type=kernel_type, interpret=True,
    )
    ref = kernel_matmul_ref(X, M, 0.7, 1.3, 0.05, kernel_type=kernel_type)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dtypes(dtype):
    X = jax.random.normal(jax.random.PRNGKey(0), (256, 8)).astype(dtype)
    M = jax.random.normal(jax.random.PRNGKey(1), (256, 16)).astype(dtype)
    out = fused_kernel_matmul(
        X, M, jnp.float32(1.0), jnp.float32(1.0), jnp.float32(0.1), interpret=True
    )
    ref = kernel_matmul_ref(
        X.astype(jnp.float32), M.astype(jnp.float32), 1.0, 1.0, 0.1
    )
    tol = 5e-2 if dtype == jnp.bfloat16 else 2e-4
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


def test_ard_lengthscale():
    X = jax.random.normal(jax.random.PRNGKey(2), (128, 5))
    M = jax.random.normal(jax.random.PRNGKey(3), (128, 4))
    ell = jnp.array([0.3, 0.5, 1.0, 2.0, 0.8])
    out = fused_kernel_matmul(
        X, M, ell, jnp.float32(2.0), jnp.float32(0.0), interpret=True
    )
    ref = kernel_matmul_ref(X, M, ell, 2.0, 0.0)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_vector_rhs():
    X = jax.random.normal(jax.random.PRNGKey(4), (200, 3))
    m = jax.random.normal(jax.random.PRNGKey(5), (200,))
    out = fused_kernel_matmul(
        X, m, jnp.float32(0.5), jnp.float32(1.0), jnp.float32(0.01), interpret=True
    )
    ref = kernel_matmul_ref(X, m[:, None], 0.5, 1.0, 0.01)[:, 0]
    assert out.shape == (200,)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_block_shape_invariance():
    """Different BlockSpec tilings must give identical results."""
    X = jax.random.normal(jax.random.PRNGKey(6), (512, 6))
    M = jax.random.normal(jax.random.PRNGKey(7), (512, 8))
    outs = [
        fused_kernel_matmul(
            X, M, jnp.float32(0.9), jnp.float32(1.1), jnp.float32(0.02),
            bn=bn, bm=bm, interpret=True,
        )
        for bn, bm in [(128, 128), (256, 512), (512, 256)]
    ]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


def test_operator_integration():
    """KernelOperator(mode='pallas') == mode='dense' through the engine."""
    from repro.gp import KernelOperator, RBFKernel

    X = jax.random.normal(jax.random.PRNGKey(8), (192, 4))
    M = jax.random.normal(jax.random.PRNGKey(9), (192, 8))
    kern = RBFKernel(lengthscale=jnp.float32(0.6), outputscale=jnp.float32(1.4))
    dense = KernelOperator(kernel=kern, X=X, mode="dense").matmul(M)
    pallas = KernelOperator(kernel=kern, X=X, mode="pallas").matmul(M)
    np.testing.assert_allclose(pallas, dense, rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("n", [100, 257, 384])
def test_edge_masking_odd_sizes(n):
    """No host-side padding of M, no n % block == 0 restriction: the kernel
    masks partial edge blocks internally."""
    X = jax.random.normal(jax.random.PRNGKey(10), (n, 5))
    M = jax.random.normal(jax.random.PRNGKey(11), (n, 3))
    out = fused_kernel_matmul(
        X, M, jnp.float32(0.8), jnp.float32(1.1), jnp.float32(0.03),
        bn=64, bm=64, interpret=True,
    )
    ref = kernel_matmul_ref(X, M, 0.8, 1.1, 0.03)
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_row_offset_partitioning():
    """Row shards with global row_offset reassemble to the full product —
    the single-host form of the device row partitioning, σ² diagonal placed
    at global coordinates."""
    from repro.kernels.kernel_matmul.ops import (
        fused_kernel_matmul_prescaled,
        prescale_inputs,
    )

    n, shards = 120, 3
    X = jax.random.normal(jax.random.PRNGKey(12), (n, 4))
    M = jax.random.normal(jax.random.PRNGKey(13), (n, 6))
    Xs = prescale_inputs(X, jnp.float32(0.7))
    full = fused_kernel_matmul(
        X, M, jnp.float32(0.7), jnp.float32(1.2), jnp.float32(0.5), interpret=True
    )
    n_loc = n // shards
    parts = [
        fused_kernel_matmul_prescaled(
            Xs[i * n_loc : (i + 1) * n_loc],
            Xs,
            M,
            jnp.float32(1.2),
            jnp.float32(0.5),
            row_offset=i * n_loc,
            interpret=True,
        )
        for i in range(shards)
    ]
    np.testing.assert_allclose(jnp.concatenate(parts, 0), full, rtol=1e-5, atol=1e-5)


def test_prepare_hoists_prescaling():
    """KernelOperator.prepare() pre-scales X once; the prepared operator's
    matmul matches the unprepared one (ARD lengthscale included)."""
    from repro.gp import KernelOperator, RBFKernel

    X = jax.random.normal(jax.random.PRNGKey(14), (130, 5))
    M = jax.random.normal(jax.random.PRNGKey(15), (130, 4))
    kern = RBFKernel(
        lengthscale=jnp.array([0.3, 0.5, 1.0, 2.0, 0.8]), outputscale=jnp.float32(1.7)
    )
    op = KernelOperator(kernel=kern, X=X, mode="pallas")
    prepared = op.prepare()
    assert type(prepared).__name__ == "PreparedPallasKernelOperator"
    np.testing.assert_allclose(prepared.matmul(M), op.matmul(M), rtol=1e-5, atol=1e-6)
    # accessors the preconditioner needs still work on the prepared operator
    np.testing.assert_allclose(prepared.diagonal(), op.diagonal(), rtol=1e-6)
    np.testing.assert_allclose(prepared.row(7), op.row(7), rtol=1e-5, atol=1e-6)


def test_engine_through_pallas_ard():
    """Full MLL through the pallas path (prepare() hoist inside the engine)
    with ARD lengthscales == dense path."""
    from repro.core import AddedDiagOperator, BBMMSettings, marginal_log_likelihood
    from repro.gp import KernelOperator, RBFKernel

    X = jax.random.normal(jax.random.PRNGKey(16), (96, 3))
    y = jnp.sin(X @ jnp.ones(3))
    kern = RBFKernel(lengthscale=jnp.array([0.5, 0.9, 1.4]), outputscale=jnp.float32(1.0))
    key = jax.random.PRNGKey(17)
    s = BBMMSettings(num_probes=8, max_cg_iters=64, precond_rank=0, cg_tol=1e-9)
    mll_d = marginal_log_likelihood(
        AddedDiagOperator(KernelOperator(kernel=kern, X=X, mode="dense"), 0.1), y, key, s
    )
    mll_p = marginal_log_likelihood(
        AddedDiagOperator(KernelOperator(kernel=kern, X=X, mode="pallas"), 0.1), y, key, s
    )
    np.testing.assert_allclose(float(mll_p), float(mll_d), rtol=1e-4)


@pytest.mark.parametrize("n,t,b", [(64, 4, 2), (100, 3, 3), (257, 5, 4)])
def test_native_batch_grid_matches_references(n, t, b):
    """(b, n, t) RHS runs as ONE pallas_call with a native batch grid dim.
    It must match (i) the vmapped formulation it replaced, (ii) the
    unbatched kernel per slice, and (iii) the jnp oracle — to f32 tolerance,
    including non-multiple-of-block n."""
    X = jax.random.normal(jax.random.PRNGKey(18), (n, 3))
    M = jax.random.normal(jax.random.PRNGKey(19), (b, n, t))
    args = (jnp.float32(0.6), jnp.float32(1.0), jnp.float32(0.1))
    out = fused_kernel_matmul(X, M, *args, bn=64, bm=64, interpret=True)
    assert out.shape == (b, n, t)
    vmapped = jax.vmap(
        lambda m: fused_kernel_matmul(X, m, *args, bn=64, bm=64, interpret=True)
    )(M)
    np.testing.assert_allclose(out, vmapped, rtol=1e-5, atol=1e-5)
    for i in range(b):
        per_slice = fused_kernel_matmul(X, M[i], *args, bn=64, bm=64, interpret=True)
        np.testing.assert_allclose(out[i], per_slice, rtol=1e-5, atol=1e-5)
        ref = kernel_matmul_ref(X, M[i], 0.6, 1.0, 0.1)
        np.testing.assert_allclose(out[i], ref, rtol=2e-4, atol=2e-4)


def test_native_batch_grid_row_offset():
    """The batch grid composes with row_offset: row shards of a batched
    product reassemble to the full batched product (the sharded path's
    batched execution)."""
    from repro.kernels.kernel_matmul.ops import (
        fused_kernel_matmul_prescaled,
        prescale_inputs,
    )

    n, shards, b = 120, 3, 2
    X = jax.random.normal(jax.random.PRNGKey(20), (n, 4))
    M = jax.random.normal(jax.random.PRNGKey(21), (b, n, 6))
    Xs = prescale_inputs(X, jnp.float32(0.7))
    full = fused_kernel_matmul_prescaled(
        Xs, Xs, M, jnp.float32(1.2), jnp.float32(0.5), interpret=True
    )
    n_loc = n // shards
    parts = [
        fused_kernel_matmul_prescaled(
            Xs[i * n_loc : (i + 1) * n_loc],
            Xs,
            M,
            jnp.float32(1.2),
            jnp.float32(0.5),
            row_offset=i * n_loc,
            interpret=True,
        )
        for i in range(shards)
    ]
    np.testing.assert_allclose(jnp.concatenate(parts, axis=1), full, rtol=1e-5, atol=1e-5)


def test_tile_load_accounting():
    """The native batch grid's X index maps ignore the batch coordinate: X
    tiles are fetched once per (i, j) grid tile, b× fewer than vmap pays."""
    from repro.kernels.kernel_matmul.kernel_matmul import tile_load_counts

    counts = tile_load_counts(256, 256, 4, t=8, bn=64, bm=64)
    assert counts["vmapped_x_tile_loads"] == 4 * counts["native_x_tile_loads"]
    assert counts["x_load_ratio"] == 4


@pytest.mark.mixed_precision
def test_mixed_precision_kernel_close_to_f32():
    """compute_dtype='bfloat16': bf16 MXU operands, f32 accumulation.
    Documented tolerance: 2e-2 relative against the f32 kernel (bf16 has an
    8-bit mantissa; errors enter through the x·xᵀ inner products and the
    tile×RHS product, never the accumulator)."""
    X = jax.random.normal(jax.random.PRNGKey(22), (200, 5))
    M = jax.random.normal(jax.random.PRNGKey(23), (200, 7))
    args = (jnp.float32(0.8), jnp.float32(1.1), jnp.float32(0.05))
    f32 = fused_kernel_matmul(X, M, *args, interpret=True)
    b16 = fused_kernel_matmul(X, M, *args, interpret=True, compute_dtype="bfloat16")
    assert b16.dtype == jnp.float32
    rel = float(jnp.linalg.norm(b16 - f32) / jnp.linalg.norm(f32))
    assert rel < 2e-2, rel
    # the precision aliases resolve to the same kernels
    mixed = fused_kernel_matmul(X, M, *args, interpret=True, compute_dtype="mixed")
    np.testing.assert_array_equal(mixed, b16)


@pytest.mark.mixed_precision
@pytest.mark.parametrize("n,t,b", [(100, 3, 3)])
def test_mixed_precision_batched_tolerance(n, t, b):
    """Native batch grid at bf16: per-slice agreement with the unbatched
    bf16 kernel stays exact (same arithmetic), f32 agreement within the
    documented 2e-2."""
    X = jax.random.normal(jax.random.PRNGKey(24), (n, 3))
    M = jax.random.normal(jax.random.PRNGKey(25), (b, n, t))
    args = (jnp.float32(0.6), jnp.float32(1.0), jnp.float32(0.1))
    b16 = fused_kernel_matmul(
        X, M, *args, bn=64, bm=64, interpret=True, compute_dtype="bfloat16"
    )
    f32 = fused_kernel_matmul(X, M, *args, bn=64, bm=64, interpret=True)
    for i in range(b):
        per_slice = fused_kernel_matmul(
            X, M[i], *args, bn=64, bm=64, interpret=True, compute_dtype="bfloat16"
        )
        np.testing.assert_allclose(b16[i], per_slice, rtol=1e-6, atol=1e-6)
    rel = float(jnp.linalg.norm(b16 - f32) / jnp.linalg.norm(f32))
    assert rel < 2e-2, rel


@pytest.mark.mixed_precision
def test_prepared_operator_mixed_precision():
    """KernelOperator.with_compute_dtype threads bf16 through prepare():
    the prepared Xs is stored half-width and the matmul stays within the
    documented tolerance of the f32 path."""
    from repro.gp import KernelOperator, RBFKernel

    X = jax.random.normal(jax.random.PRNGKey(26), (130, 5))
    M = jax.random.normal(jax.random.PRNGKey(27), (130, 4))
    kern = RBFKernel(
        lengthscale=jnp.array([0.3, 0.5, 1.0, 2.0, 0.8]), outputscale=jnp.float32(1.7)
    )
    op = KernelOperator(kernel=kern, X=X, mode="pallas")
    mixed = op.with_compute_dtype("mixed").prepare()
    assert mixed.Xs.dtype == jnp.bfloat16
    f32 = op.prepare().matmul(M)
    rel = float(jnp.linalg.norm(mixed.matmul(M) - f32) / jnp.linalg.norm(f32))
    assert rel < 2e-2, rel


# ---------------------------------------------------------------------------
# precision="highest" as packed bf16 splits
# ---------------------------------------------------------------------------


def _eqns(jaxpr):
    """Every eqn of a jaxpr and of its sub-jaxprs (pjit bodies, kernels)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _launches(jaxpr):
    """(pallas_call eqn, dot_general eqns of its kernel) per launch."""
    return [
        (e, [d for d in _eqns(e.params["jaxpr"]) if d.primitive.name == "dot_general"])
        for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"
    ]


def _three_term(a, b):
    """hi·hi + hi·mid + mid·hi of two f32 arrays' splits, f32 accumulation:
    the negative control, 3 of HIGHEST's 6 terms."""
    from repro.kernels.kernel_matmul.kernel_matmul import split_bf16

    (ah, am, _), (bh, bm, _) = split_bf16(a), split_bf16(b)
    dot = lambda x, y: jax.lax.dot_general(  # noqa: E731
        x, y, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return dot(ah, bh) + dot(ah, bm) + dot(am, bh)


def _packed_inner(x1, x2):
    """The distance stage as the kernel runs it: one bf16 pass over the
    packed splits, f32 accumulation."""
    from repro.kernels.kernel_matmul.ops import pack_split_operands

    packed = pack_split_operands(x1, x2)
    return jax.lax.dot_general(
        packed.rows, packed.cols, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def _packed_product(k, m):
    """The product stage as the kernel and its wrapper run it: three passes
    of the tile's pieces against [M_hi | M_mid | M_lo], lane groups summed."""
    from repro.kernels.kernel_matmul.kernel_matmul import _tile_rhs_product, split_bf16

    t = m.shape[1]
    mp = jnp.concatenate(split_bf16(m, rounded=False), axis=1)
    out = _tile_rhs_product(k, mp, 0, m.shape[0], m.shape[0], jnp.float32)
    return (out[:, 2 * t :] + out[:, t : 2 * t]) + out[:, :t]


def _stage_operands(stage, width):
    """f32 operands of one MXU stage, as (a, b) of a (r, w) @ (w, c) product:
    distances (x1, x2ᵀ) of ARD-scaled normals; the product (K, M) with K a
    positive Matérn-like tile."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(width + (0 if stage == "distance" else 99)))
    if stage == "distance":
        x1 = jax.random.normal(k1, (96, width)) / 0.7
        x2 = jax.random.normal(k2, (160, width)) / 0.7
        return x1, x2
    k = jnp.exp(-jax.random.uniform(k1, (96, 160), maxval=6.0))
    return k, jax.random.normal(k2, (160, width))


@pytest.mark.parametrize("terms", ["split", "three-term control"])
@pytest.mark.parametrize(
    "stage,width",
    [("distance", d) for d in (3, 18, 22, 40)] + [("product", t) for t in (1, 11, 42)],
)
def test_split_stage_matches_f32(stage, width, terms):
    """Each packed MXU stage against float64: within 4× the max error of a
    true-f32 dot of the same operands (floored at 2⁻²³ of the operands'
    scale).  The control — only hi·hi + hi·mid + mid·hi — must miss the same
    bound, or the bound could not tell bf16 splits from f32."""
    a, b = _stage_operands(stage, width)
    a64, b64 = np.float64(a), np.float64(b)
    if stage == "distance":
        exact, f32 = a64 @ b64.T, jnp.dot(a, b.T, precision="highest")
        scale = np.abs(a64) @ np.abs(b64).T
        packed = _packed_inner(a, b) if terms == "split" else _three_term(a, b.T)
    else:
        exact, f32 = a64 @ b64, jnp.dot(a, b, precision="highest")
        scale = np.abs(a64) @ np.abs(b64)
        packed = _packed_product(a, b) if terms == "split" else _three_term(a, b)
    bound = max(4 * np.abs(np.float64(f32) - exact).max(), 2.0**-23 * scale.max())
    err = np.abs(np.float64(packed) - exact).max()
    if terms == "split":
        assert err <= bound, (err, bound)
    else:
        assert err > bound, (err, bound)


def _highest(Xs, M, outputscale, sigma2, **kw):
    """The pre-split f32 launch: f32 operands in 128 lanes, both MXU stages
    at Precision.HIGHEST."""
    from repro.kernels.kernel_matmul.kernel_matmul import kernel_matmul_pallas

    Xp = jnp.pad(Xs, ((0, 0), (0, (-Xs.shape[1]) % 128)))
    return kernel_matmul_pallas(
        Xp, Xp, M, jnp.float32(outputscale), jnp.float32(sigma2), interpret=True, **kw
    )


def _product64(kernel_type, X, M, outputscale, sigma2):
    """(K(X, X) + σ²I) @ M and Σ|K||M| in float64, distances from differences."""
    X = np.float64(X)
    d = np.sqrt(np.sum((X[:, None] - X[None]) ** 2, -1))
    a = {"matern32": np.sqrt(3.0), "matern52": np.sqrt(5.0)}.get(kernel_type, 1.0) * d
    k = {
        "rbf": np.exp(-0.5 * d * d),
        "matern12": np.exp(-d),
        "matern32": (1 + a) * np.exp(-a),
        "matern52": (1 + a + a * a / 3) * np.exp(-a),
    }[kernel_type]
    k = outputscale * k + sigma2 * np.eye(len(X))
    M = np.float64(M)
    return k @ M, k @ np.abs(M)


@pytest.mark.parametrize("case", ["odd-n", "row-offset", "batched"])
@pytest.mark.parametrize("kernel_type", ["rbf", "matern12", "matern32", "matern52"])
def test_split_kernel_matches_highest(kernel_type, case):
    """The packed launch against the HIGHEST one, for every kernel type, on
    partial edge blocks (odd n), a row shard at a non-zero row_offset and
    the batched RHS grid: at least as close to float64 (floored at 2⁻²⁰ of
    the output's scale).  Its diagonal is exact, where HIGHEST's distance
    of a point to itself cancels only to an ulp of ‖x‖² — which Matérn-1/2
    lifts to ~1e-3."""
    from repro.kernels.kernel_matmul.ops import fused_kernel_matmul_prescaled

    n, d, t = 203, 6, 5
    X = jax.random.normal(jax.random.PRNGKey(30), (n, d)) / 0.8
    shape = (2, n, t) if case == "batched" else (n, t)
    M = jax.random.normal(jax.random.PRNGKey(31), shape)
    kw = dict(kernel_type=kernel_type, bn=64, bm=64)
    rows = slice(75, 150) if case == "row-offset" else slice(None)
    highest = _highest(X, M, 1.3, 0.07, **kw)[..., rows, :]
    out = fused_kernel_matmul_prescaled(
        X[rows], X, M, 1.3, 0.07, row_offset=rows.start or 0, interpret=True, **kw
    )
    assert out.shape == highest.shape
    exact, scale = zip(*(
        _product64(kernel_type, X, m, 1.3, 0.07) for m in M.reshape(-1, n, t)
    ))
    exact = np.stack(exact).reshape(shape)[..., rows, :]
    floor = 2.0**-20 * max(s.max() for s in scale)
    err = np.abs(np.float64(out) - exact).max()
    assert err <= max(np.abs(np.float64(highest) - exact).max(), floor), err


@pytest.mark.parametrize("t,split", [(1, True), (42, True), (43, False), (128, False)])
def test_split_dispatch_by_shape(t, split):
    """The product is packed exactly while it takes fewer passes than
    HIGHEST (t ≤ 42); the distances always are.  One launch per product,
    inside the ``mxu.split_bf16`` scope, its MXU operands bf16 on the
    packed stages and f32 on a HIGHEST product."""
    from repro.kernels.kernel_matmul.ops import (
        SPLIT_SCOPE,
        fused_kernel_matmul_prescaled,
        split_product_pays,
    )

    assert split_product_pays(t) == split
    X = jnp.ones((40, 18))
    jaxpr = jax.make_jaxpr(
        lambda m: fused_kernel_matmul_prescaled(X, X, m, 1.0, 0.1, interpret=True)
    )(jnp.ones((40, t)))
    (eqn, (dist, *prod)), = _launches(jaxpr.jaxpr)
    assert eqn.params["name"] == "kernel_matmul"
    assert SPLIT_SCOPE in str(eqn.source_info.name_stack)
    one, six = jax.lax.Precision.DEFAULT, jax.lax.Precision.HIGHEST
    assert {v.aval.dtype for v in dist.invars} == {jnp.dtype(jnp.bfloat16)}
    assert set(dist.params["precision"]) == {one}
    want = [(jnp.bfloat16, {one})] * 3 if split else [(jnp.float32, {six})]
    got = [(e.invars[0].aval.dtype, set(e.params["precision"])) for e in prod]
    assert got == want


@pytest.mark.mixed_precision
@pytest.mark.parametrize("shape", [(131, 7), (2, 131, 7)], ids=["plain", "batched"])
def test_mixed_policy_bitwise_single_pass(shape):
    """compute_dtype="bfloat16" keeps its single-pass launch bit for bit:
    bf16 X and M in 128 lanes, the norms reduced in-kernel, no split and no
    ``mxu.split_bf16`` scope."""
    from repro.kernels.kernel_matmul.kernel_matmul import kernel_matmul_pallas
    from repro.kernels.kernel_matmul.ops import SPLIT_SCOPE, fused_kernel_matmul

    X = jax.random.normal(jax.random.PRNGKey(32), (shape[-2], 5))
    M = jax.random.normal(jax.random.PRNGKey(33), shape)
    ell, s, s2 = jnp.float32(0.7), jnp.float32(1.2), jnp.float32(0.05)
    Xb = jnp.pad((X / ell).astype(jnp.bfloat16), ((0, 0), (0, 123)))
    old = kernel_matmul_pallas(
        Xb, Xb, M.astype(jnp.bfloat16), s, s2, bn=64, bm=64, interpret=True,
        compute_dtype="bfloat16", kernel_type="matern32",
    )

    def new(M):
        return fused_kernel_matmul(
            X, M, ell, s, s2, bn=64, bm=64, interpret=True,
            compute_dtype="bfloat16", kernel_type="matern32",
        )

    np.testing.assert_array_equal(new(M), old)
    (eqn, _), = _launches(jax.make_jaxpr(new)(M).jaxpr)
    assert SPLIT_SCOPE not in str(eqn.source_info.name_stack)
