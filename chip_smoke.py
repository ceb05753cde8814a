#!/usr/bin/env python3
"""Chip smoke run: the BBMM train -> cache -> serve path, compiled, on TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded paths over four chips

One chip drives an exact GP at the shape of the paper's Protein benchmark
(n=45 730, d=9, Matern-5/2 ARD, ``mode="pallas"``, 10 probes, rank-5
pivoted-Cholesky preconditioner) through ``GPModel`` -> ``fit_gp`` ->
``PosteriorSession``, with data made from ``--seed``.  Phases, in order:
device check, Pallas kernel vs ``kernel_matmul_ref``, three training
steps, cache build + queries + ``observe``, the same model at n=4 096
against a dense Cholesky, solve health.

``--chips 4`` runs only the four-chip phase: a 3DRoad-shaped exact GP
(n=430 080, d=3) in ``mode="pallas_partitioned"`` with its row bands
sharded over a 4-device mesh (one MLL + gradient, one posterior-cache
build) and one ``pallas_sharded`` kernel matmul, each against the same
computation on ``jax.devices()[0]``.

Every phase prints its numbers on lines of its own.  A failed check, a
non-finite value, a ``SolveFailure`` or a caught build fault exits
non-zero without the result line, and so does a run where JAX finds no
TPU (there is no CPU fallback).  The last line of stdout is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
from functools import partial

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.core import BBMMSettings, inv_quad_logdet  # noqa: E402
from repro.data.pipeline import RegressionStream  # noqa: E402
from repro.gp import ExactGP, fit_gp  # noqa: E402
from repro.kernels.kernel_matmul.ops import (  # noqa: E402
    fused_kernel_matmul,
    sharded_kernel_matmul,
)
from repro.kernels.kernel_matmul.ref import kernel_matmul_ref  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.serving import CircuitBreaker, PosteriorSession  # noqa: E402

# Protein-shaped configuration (UCI protein: n=45 730, d=9)
N, D = 45_730, 9
NUM_PROBES, PRECOND_RANK = 10, 5
N_REF = 4_096  # the dense-Cholesky reference size
QUERY_BATCH, NUM_QUERIES, NUM_OBSERVE = 256, 4, 64
TRAIN_STEPS = 3
# 3DRoad-shaped four-chip configuration (d=3; n rounded so every band of
# the 4-way split is a whole number of 512-row panels).  The 1-vs-4-device
# comparison runs 5 CG iterations: unconverged f32 CG is sensitive to the
# order of its reductions, and past ~5 iterations any two orders drift
# apart by far more than f32 rounding (on 4 CPU devices at n=8 192: MLL
# 2e-5 apart after 3-5 iterations, 2e-3 after 10, 4e-2 after 20).
N4, D4, PANEL_ROWS4, CG_ITERS4 = 430_080, 3, 512, 5

# Tolerances of the CPU tests for the same quantities
KERNEL_TOL = {"float32": 2e-4, "bfloat16": 2e-2}  # test_kernel_matmul_pallas
MLL_RTOL = 0.03  # test_inference_engine::test_value_matches_dense
INV_QUAD_RTOL = 1e-3  # test_inference_engine::test_inv_quad_exact
MEAN_RTOL = MEAN_ATOL = 1e-3  # test_gp_models::test_interpolation_quality_vs_cholesky
# the reference solves at the CPU tests' CG budget, not the 20-iteration
# training budget: the tolerances above assume a converged solve
REF_CG = dict(max_cg_iters=100, cg_tol=1e-6)
SHARD_RTOL = 1e-4  # f32, test_partitioned 1-vs-8-device comparisons
SHARD_GRAD_RTOL = 1e-3


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(tag: str, **fields) -> None:
    print(f"[{tag}] " + " ".join(f"{k}={v}" for k, v in fields.items()), flush=True)


def finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree_util.tree_leaves(tree))


def rel_err(a, b) -> float:
    """max |a - b| / max |b| (unrounded)."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def protein_model() -> ExactGP:
    return ExactGP(
        kernel_type="matern52", mode="pallas", ard=True,
        settings=BBMMSettings(num_probes=NUM_PROBES, precond_rank=PRECOND_RANK),
    )


class StepClock(obs.MetricsRegistry):
    """Registry that also keeps each ``fit_step_seconds`` sample in order."""

    def __init__(self):
        super().__init__()
        self.steps: list[float] = []

    def observe(self, name, value, **kw):
        if name == "fit_step_seconds":
            self.steps.append(float(value))
        super().observe(name, value, **kw)


# -- phases ---------------------------------------------------------------


def device_check(chips: int):
    devs = jax.devices()
    platform = devs[0].platform
    check(platform == "tpu", f"no TPU: jax.devices()[0].platform={platform!r}")
    check(len(devs) >= chips, f"{chips} chips asked for, {len(devs)} found")
    cache = configure_compile_cache()
    warm = os.path.isdir(cache) and len(os.listdir(cache))
    emit("device", platform=platform, kind=devs[0].device_kind, count=len(devs),
         compile_cache=cache, cache_entries_at_start=warm or 0)
    return devs


def phase_kernel(X, kern, seed: int, t: int, bn: int = 256) -> None:
    """The Pallas kernel, compiled, vs the materialized reference on the
    first row block and the ragged last one."""
    n = X.shape[0]
    M = jax.random.normal(jax.random.PRNGKey(seed + 1), (n, t))
    last = (n - 1) // bn * bn
    rows = np.r_[0:bn, last:n]
    args = (X, M, kern.lengthscale, kern.outputscale, jnp.float32(0.0))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(partial(kernel_matmul_ref, kernel_type="matern52"))(
            *args, rows=rows
        )
    for dtype, tol in KERNEL_TOL.items():
        f = jax.jit(partial(fused_kernel_matmul, kernel_type="matern52",
                            compute_dtype=dtype))
        t0 = time.perf_counter()
        compiled = f.lower(*args).compile()
        compile_s = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"kernel ({dtype}): no tpu_custom_call in the compiled HLO")
        jax.block_until_ready(compiled(*args))
        out, run_s = timed(compiled, *args)
        check(finite(out), f"kernel ({dtype}): non-finite output")
        err = rel_err(out[rows], ref)
        emit("kernel", compute_dtype=dtype, n=n, t=t, rows_checked=len(rows),
             max_rel_err=err, tol=tol, tpu_custom_call=True,
             compile_s=compile_s, run_s=run_s)
        check(err <= tol, f"kernel ({dtype}): max_rel_err {err} > {tol}")


def phase_train(model, X, y, seed: int, steps: int):
    """fit_gp through the compiled kernel; compile time apart from steps."""
    key = jax.random.PRNGKey(seed + 2)
    params0 = model.init_params(X)
    vg = jax.jit(jax.value_and_grad(model.loss))
    t0 = time.perf_counter()
    compiled = vg.lower(params0, X, y, key).compile()
    compile_s = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(),
          "train: no tpu_custom_call in the compiled MLL-gradient step")
    (loss0, grads0), grad_s = timed(compiled, params0, X, y, key)
    check(finite((loss0, grads0)), "train: non-finite loss/gradient at init")
    emit("train", phase="value_and_grad", tpu_custom_call=True,
         compile_s=compile_s, run_s=grad_s, loss=float(loss0))

    clock = StepClock()
    with obs.installed(clock):
        params, hist = fit_gp(model, X, y, steps=steps, key=key)
    check(len(hist) == steps and all(math.isfinite(h) for h in hist),
          f"train: non-finite losses {hist}")
    check(finite(params), "train: non-finite parameters")
    for i, (loss, sec) in enumerate(zip(hist, clock.steps)):
        emit("train", step=i, loss=loss, step_s=sec,
             includes_compile=(i == 0))
    steady = clock.steps[1:]
    emit("train", steps=steps, mode=model.mode, first_step_s=clock.steps[0],
         steady_step_s=sum(steady) / len(steady))
    return params


def phase_serve(model, params, X, y, queries, X_new, y_new) -> list:
    """PosteriorSession: build, NUM_QUERIES batches, observe, one more."""
    t0 = time.perf_counter()
    session = PosteriorSession(model, params, X, y)
    jax.block_until_ready(jax.tree_util.tree_leaves(session.cache))
    emit("serve", phase="cache_build", n=session.n,
         build_s=time.perf_counter() - t0)
    for i, Xq in enumerate(queries):
        if i == len(queries) - 1:
            t0 = time.perf_counter()
            path = session.observe(X_new, y_new)
            jax.block_until_ready(jax.tree_util.tree_leaves(session.cache))
            emit("serve", phase="observe", rows=X_new.shape[0], path=path,
                 n=session.n, observe_s=time.perf_counter() - t0)
        (mean, var), q_s = timed(session.query, Xq)
        check(finite((mean, var)), f"serve: non-finite prediction in batch {i}")
        check(mean.shape == (Xq.shape[0],) and var.shape == mean.shape,
              f"serve: prediction shapes {mean.shape} {var.shape}")
        emit("serve", phase="query", batch=i, points=Xq.shape[0],
             query_s=q_s, after_observe=i == len(queries) - 1,
             min_var=float(jnp.min(var)))
    stats = session.health_stats()
    emit("serve", degraded_queries=stats["degraded_queries"],
         rebuild_failures=stats["rebuild_failures"],
         breaker_state=stats["breaker_state"],
         cache_version=session.cache_info.version,
         staleness=session.cache_info.staleness)
    check(stats["degraded_queries"] == 0, "serve: degraded queries")
    check(stats["rebuild_failures"] == 0, "serve: rebuild failures")
    check(stats["breaker_state"] == CircuitBreaker.CLOSED, "serve: breaker not closed")
    check(not session.cache_info.degraded, "serve: cache flagged degraded")
    return list(session.health_reports)


def phase_reference(model, params, seed: int, n: int) -> None:
    """The same model at small n vs a dense Cholesky at highest precision."""
    model = dataclasses.replace(
        model, settings=dataclasses.replace(model.settings, **REF_CG)
    )
    X, y = RegressionStream(n=n + QUERY_BATCH, d=D, seed=seed + 7).dataset()
    X, y, Xq = X[:n], y[:n], X[n:]
    key = jax.random.PRNGKey(seed + 3)
    iq, ld = jax.jit(
        lambda p: inv_quad_logdet(model.operator(p, X), y, key, model.settings)
    )(params)
    cache = model.posterior_cache(params, X, y)
    mean, _ = model.predict_cached(params, X, cache, Xq)
    with jax.default_matmul_precision("highest"):
        kern = model.kernel(params)
        K = kern(X, X) + model.noise(params) * jnp.eye(n)
        L = jnp.linalg.cholesky(K)
        alpha = jax.scipy.linalg.cho_solve((L, True), y)
        iq_ref = y @ alpha
        ld_ref = 2.0 * jnp.sum(jnp.log(jnp.diagonal(L)))
        mean_ref = kern(Xq, X) @ alpha
    const = n * math.log(2 * math.pi)
    mll, mll_ref = -0.5 * (float(iq) + float(ld) + const), -0.5 * (
        float(iq_ref) + float(ld_ref) + const)
    iq_err = abs(float(iq) - float(iq_ref)) / abs(float(iq_ref))
    ld_err = abs(float(ld) - float(ld_ref)) / abs(float(ld_ref))
    mean_abs = float(jnp.max(jnp.abs(mean - mean_ref)))
    mean_ok = bool(jnp.all(jnp.abs(mean - mean_ref)
                           <= MEAN_ATOL + MEAN_RTOL * jnp.abs(mean_ref)))
    mll_err = abs(mll - mll_ref) / abs(mll_ref)
    emit("reference", n=n, mll=mll, mll_cholesky=mll_ref,
         mll_rel_err=mll_err, mll_rtol=MLL_RTOL,
         inv_quad_rel_err=iq_err, inv_quad_rtol=INV_QUAD_RTOL,
         logdet_rel_err=ld_err,
         mean_max_abs_err=mean_abs, mean_rtol=MEAN_RTOL, mean_atol=MEAN_ATOL,
         **REF_CG)
    check(finite((iq, ld, mean)), "reference: non-finite BBMM values")
    check(iq_err <= INV_QUAD_RTOL, f"reference: inv_quad rel err {iq_err}")
    check(mll_err <= MLL_RTOL, f"reference: MLL rel err {mll_err}")
    check(mean_ok, f"reference: posterior mean max abs err {mean_abs}")


def phase_health(reports) -> None:
    """Every solve-health verdict of the serving phase, one per line."""
    check(reports, "health: no solve report was collected")
    for r in reports:
        emit("health", context=r.context, status=r.status, iters=r.num_iters,
             max_iters=r.max_iters, residual=r.residual_norm, tol=r.tol,
             degraded=r.degraded)
    check(all(not r.degraded for r in reports), "health: a degraded rung ran")


def one_chip(seed: int):
    devs = device_check(1)
    X_all, y_all = RegressionStream(
        n=N + NUM_OBSERVE + NUM_QUERIES * QUERY_BATCH + QUERY_BATCH, d=D, seed=seed
    ).dataset()
    X, y = X_all[:N], y_all[:N]
    X_new, y_new = X_all[N:N + NUM_OBSERVE], y_all[N:N + NUM_OBSERVE]
    rest = X_all[N + NUM_OBSERVE:]
    queries = [rest[i * QUERY_BATCH:(i + 1) * QUERY_BATCH]
               for i in range(NUM_QUERIES + 1)]
    model = protein_model()
    emit("config", n=N, d=D, kernel="matern52_ard", mode=model.mode,
         num_probes=NUM_PROBES, precond_rank=PRECOND_RANK,
         max_cg_iters=model.settings.max_cg_iters, cg_tol=model.settings.cg_tol,
         seed=seed)

    phase_kernel(X, model.kernel(model.init_params(X)), seed, NUM_PROBES + 1)
    params = phase_train(model, X, y, seed, TRAIN_STEPS)
    reports = phase_serve(model, params, X, y, queries, X_new, y_new)
    phase_reference(model, params, seed, N_REF)
    phase_health(reports)
    return devs


def compare(name, sharded, single, rtol) -> None:
    bitwise = bool(np.array_equal(np.asarray(sharded), np.asarray(single)))
    err = rel_err(sharded, single)
    emit("chips4", compare=name, bitwise=bitwise, max_rel_err=err, rtol=rtol)
    check(bitwise or err <= rtol, f"chips4: {name} max_rel_err {err} > {rtol}")


def spread(arr) -> int:
    return len(arr.sharding.device_set)


def four_chips(seed: int):
    """Sharded partitioned MLL + gradient and cache build, and the
    pallas_sharded matmul, each vs the same computation on devices[0]."""
    devs = device_check(4)
    mesh = make_mesh((4,), ("data",), devices=devs[:4])
    X, y = RegressionStream(n=N4, d=D4, seed=seed).dataset()
    model = ExactGP(
        kernel_type="matern52", mode="pallas_partitioned", panel_backend="pallas",
        ard=True,
        settings=BBMMSettings(num_probes=NUM_PROBES, precond_rank=PRECOND_RANK,
                              panel_rows=PANEL_ROWS4, max_cg_iters=CG_ITERS4),
    )
    emit("config", n=N4, d=D4, kernel="matern52_ard", mode=model.mode,
         panel_rows=PANEL_ROWS4, max_cg_iters=CG_ITERS4, devices=4, seed=seed)
    params = model.init_params(X)
    key = jax.random.PRNGKey(seed + 2)
    vg = jax.jit(jax.value_and_grad(model.loss))
    M = jax.random.normal(jax.random.PRNGKey(seed + 1), (N4, NUM_PROBES + 1))
    kern = model.kernel(params)

    out = {}
    for where in ("devices[0]", "mesh4"):
        if where == "mesh4":
            ctx = jax.set_mesh(mesh)
            matmul = partial(sharded_kernel_matmul, kern, X, M, mesh)
        else:
            ctx = jax.default_device(devs[0])
            matmul = partial(fused_kernel_matmul, X, M, kern.lengthscale,
                             kern.outputscale, jnp.float32(0.0),
                             kernel_type="matern52")
        with ctx:
            (loss, grads), grad_s = timed(vg, params, X, y, key)
            cache, cache_s = timed(model.posterior_cache, params, X, y)
            mm, mm_s = timed(matmul)
        check(finite((loss, grads, cache.alpha, mm)), f"chips4 ({where}): non-finite")
        emit("chips4", where=where, loss=float(loss), grad_s=grad_s,
             cache_build_s=cache_s, matmul_s=mm_s, s_include_compile=True,
             alpha_devices=spread(cache.alpha), matmul_devices=spread(mm),
             matmul_sharding=mm.sharding)
        out[where] = (loss, grads, cache.alpha, mm)
    (l1, g1, a1, m1), (l4, g4, a4, m4) = out["devices[0]"], out["mesh4"]
    check(spread(m4) == 4, "chips4: pallas_sharded output not on 4 devices")
    check(spread(a4) == 4, "chips4: sharded cache solve not on 4 devices")
    compare("mll", l4, l1, SHARD_RTOL)
    for k in g1:
        compare(f"grad[{k}]", g4[k], g1[k], SHARD_GRAD_RTOL)
    compare("cache_alpha", a4, a1, SHARD_GRAD_RTOL)
    compare("pallas_sharded_matmul", m4, m1, SHARD_RTOL)
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        devs = four_chips(args.seed) if args.chips == 4 else one_chip(args.seed)
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
