"""GP serving driver: batched posterior queries + interleaved streaming
observations through a :class:`repro.serving.PosteriorSession`.

    PYTHONPATH=src python -m repro.launch.gp_serve --model sgpr \
        --n 2000 --requests 40 --batch 256 --observe-every 8

Simulates the serving-traffic pattern the ROADMAP targets: a request loop
answering batched mean/variance queries entirely from the posterior cache
(zero CG iterations per request), periodically interrupted by new
observations that are folded in *incrementally* — an exact rank-k
Woodbury refresh for SGPR/BLR (no CG at all), warm-started CG with
Krylov-basis recycling for ExactGP/DKL/MultitaskGP, full rebuild for SKI —
under the session's ``max_staleness`` policy.  Reports cached QPS (query
points per second) and the append-vs-rebuild latency split.

``--threads N`` switches to the **thread-pool request driver**: N worker
threads issue query batches concurrently while the main thread streams
observations and kicks double-buffered refreshes
(``session.rebuild_async``) onto a dedicated refresher worker — vN keeps
serving under the concurrent load while vN+1 builds, and buffers that a
mid-build mutation made stale are discarded instead of swapped (counted
in the report).

``--model multitask`` serves a :class:`repro.gp.MultitaskGP` over
long-format (x, task) rows — queries carry a task column and streamed
observations append complete task blocks (the Kronecker-preserving case).

``--chaos`` runs the **fault-injection drill** over the threaded driver:
a seeded :class:`repro.core.FaultSchedule` corrupts the kernel matmuls
mid-serve (NaN in the reduced-precision path, then a total outage) while
query workers keep hammering the session.  The drill asserts the whole
robustness stack end-to-end — the degradation ladder's
``precision_f32`` escalation heals the mixed-precision NaNs, the circuit
breaker opens under the outage and queries degrade to the last
consistent cache instead of erroring, and the breaker re-closes on
recovery — and exits nonzero if any query raised, no escalation was
recorded, or no degraded query was served.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
import threading
import time
import warnings
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import (
    AddedDiagOperator,
    BBMMSettings,
    FaultInjectingOperator,
    FaultSchedule,
    build_posterior_cache,
    extend_posterior_cache,
)
from repro.core.health import SolveHealthWarning
from repro.gp import (
    SGPR,
    SKI,
    BayesianLinearRegression,
    DKLExactGP,
    ExactGP,
    MultitaskGP,
    to_long_format,
)
from repro.launch.compile_cache import configure_compile_cache
from repro.serving import CircuitBreaker, PosteriorSession

MODELS = ("exact", "sgpr", "ski", "dkl", "blr", "multitask")


def build_model(
    name: str,
    *,
    max_cg_iters: int = 25,
    precision: str | None = None,
    num_tasks: int = 2,
):
    settings = BBMMSettings(num_probes=8, max_cg_iters=max_cg_iters)
    if name == "exact":
        return ExactGP(settings=settings, precision=precision)
    if name == "sgpr":
        return SGPR(num_inducing=64, precision=precision)
    if name == "ski":
        return SKI(grid_size=64, settings=settings, precision=precision)
    if name == "dkl":
        return DKLExactGP(hidden=(16, 2), settings=settings, precision=precision)
    if name == "blr":
        return BayesianLinearRegression(precision=precision)
    if name == "multitask":
        # task-kernel preconditioning is a documented frontier: rank 0
        return MultitaskGP(
            num_tasks=num_tasks,
            settings=BBMMSettings(
                num_probes=8, max_cg_iters=max_cg_iters, precond_rank=0
            ),
            precision=precision,
        )
    raise ValueError(f"unknown model {name!r} ({'|'.join(MODELS)})")


def _task_targets(coords, T, key):
    """Per-task targets: one shared latent signal, task-specific scale."""
    latent = jnp.sin(3 * coords[:, 0]) * jnp.cos(2 * coords[:, -1])
    scales = 1.0 + 0.3 * jnp.arange(T)
    return latent[:, None] * scales[None, :] + 0.05 * jax.random.normal(
        key, (coords.shape[0], T)
    )


def _toy(key, n, d, num_tasks=0):
    """(X, y) training data — long-format rows when ``num_tasks`` > 0."""
    kx, ky = jax.random.split(key)
    coords = jax.random.uniform(kx, (n, d)) * 2 - 1
    if num_tasks:
        return to_long_format(coords, _task_targets(coords, num_tasks, ky))
    y = jnp.sin(3 * coords[:, 0]) * jnp.cos(2 * coords[:, -1])
    return coords, y + 0.05 * jax.random.normal(ky, (n,))


def _query_batch(key, batch, d, num_tasks=0):
    kq, kt = jax.random.split(key)
    coords = jax.random.uniform(kq, (batch, d)) * 2 - 1
    if num_tasks:
        tasks = jax.random.randint(kt, (batch,), 0, num_tasks).astype(jnp.float32)
        return jnp.concatenate([coords, tasks[:, None]], axis=-1)
    return coords


def _observation(key, k, d, num_tasks=0):
    """k new observations — a complete task block per point for multitask
    (the Kronecker-structure-preserving append)."""
    kx, ky = jax.random.split(key)
    coords = jax.random.uniform(kx, (k, d)) * 2 - 1
    if num_tasks:
        return to_long_format(coords, _task_targets(coords, num_tasks, ky))
    yn = jnp.sin(3 * coords[:, 0]) * jnp.cos(2 * coords[:, -1])
    return coords, yn + 0.05 * jax.random.normal(ky, (k,))


def run_serve(
    *,
    model: str = "sgpr",
    n: int = 1000,
    d: int = 2,
    requests: int = 20,
    batch: int = 128,
    observe_every: int = 5,
    observe_batch: int = 1,
    max_staleness: int = 8,
    fit_steps: int = 0,
    max_cg_iters: int = 25,
    precision: str | None = None,
    num_tasks: int = 2,
    seed: int = 0,
    verbose: bool = True,
    session_hook=None,
) -> dict:
    """Drive the request loop; return the metric row (also printed).

    ``session_hook(session)`` fires once the session exists — the metrics
    endpoint uses it to wire ``/health`` to ``session.health_stats()``."""
    key = jax.random.PRNGKey(seed)
    kd, kq, ko = jax.random.split(key, 3)
    T = num_tasks if model == "multitask" else 0
    X, y = _toy(kd, n, d, T)
    gp = build_model(
        model, max_cg_iters=max_cg_iters, precision=precision, num_tasks=num_tasks
    )
    if fit_steps > 0:
        params, _ = gp.fit(X, y, steps=fit_steps)
    else:
        params = gp.init_params(X)

    t0 = time.perf_counter()
    session = PosteriorSession(gp, params, X, y, max_staleness=max_staleness)
    if session_hook is not None:
        session_hook(session)
    jax.block_until_ready(jax.tree_util.tree_leaves(session.cache))
    t_build = time.perf_counter() - t0

    # warm the query path (compile) before timing
    Xw = _query_batch(jax.random.fold_in(kq, requests + 1), batch, d, T)
    jax.block_until_ready(session.query(Xw)[0])

    q_time = 0.0
    appends, rebuilds = [], []
    for r in range(requests):
        Xq = _query_batch(jax.random.fold_in(kq, r), batch, d, T)
        t0 = time.perf_counter()
        mean, var = session.query(Xq)
        jax.block_until_ready(mean)
        q_time += time.perf_counter() - t0
        if observe_every and (r + 1) % observe_every == 0:
            Xn, yn = _observation(jax.random.fold_in(ko, r), observe_batch, d, T)
            t0 = time.perf_counter()
            path = session.observe(Xn, yn)
            # block on the UPDATED CACHE, not just the concatenated data —
            # otherwise the async-dispatched update isn't in the measurement
            jax.block_until_ready(jax.tree_util.tree_leaves(session.cache))
            dt = time.perf_counter() - t0
            (appends if path == "append" else rebuilds).append(dt)

    # the rebuild baseline the append path is measured against
    t0 = time.perf_counter()
    session.rebuild()
    jax.block_until_ready(jax.tree_util.tree_leaves(session.cache))
    t_rebuild = time.perf_counter() - t0

    qps = requests * batch / q_time if q_time > 0 else float("inf")
    # steady-state append latency: the first append pays one-off tracing /
    # compilation (constant m-space shapes for the Woodbury models), so the
    # minimum is the serving-relevant number; the mean is reported too
    append_s = min(appends) if appends else float("nan")
    append_avg_s = sum(appends) / len(appends) if appends else float("nan")
    metrics = {
        "model": f"serve_{model}",
        "n": n,
        "batch": batch,
        "requests": requests,
        "cache_build_s": t_build,
        "cached_qps": qps,
        "query_ms": q_time / requests * 1e3,
        "append_s": append_s,
        "append_avg_s": append_avg_s,
        "rebuild_s": t_rebuild,
        "append_speedup": (t_rebuild / append_s) if appends else float("nan"),
        "num_appends": len(appends),
        "num_rebuilds": len(rebuilds),
        "final_n": session.n,
        "cache_version": session.cache_info.version,
    }
    if verbose:
        print(
            f"[{model}] n={n}→{session.n}  build {t_build*1e3:.0f} ms | "
            f"{requests} x {batch}-pt queries: {qps:,.0f} pts/s "
            f"({metrics['query_ms']:.1f} ms/req, CG-free) | "
            f"observe: {len(appends)} appends "
            f"{append_s*1e3 if appends else float('nan'):.1f} ms vs rebuild "
            f"{t_rebuild*1e3:.1f} ms "
            f"({metrics['append_speedup']:.1f}x) | {len(rebuilds)} rebuilds"
        )
    return metrics


def run_serve_threaded(
    *,
    model: str = "sgpr",
    n: int = 1000,
    d: int = 2,
    requests: int = 40,
    batch: int = 128,
    observe_every: int = 8,
    observe_batch: int = 1,
    max_staleness: int = 8,
    fit_steps: int = 0,
    max_cg_iters: int = 25,
    precision: str | None = None,
    num_tasks: int = 2,
    threads: int = 4,
    seed: int = 0,
    verbose: bool = True,
    session_hook=None,
) -> dict:
    """Concurrent request driver over the double-buffered session.

    ``threads`` query workers hammer ``session.query`` while the main
    thread streams observations and schedules ``rebuild_async`` refreshes
    on a dedicated worker — serving never blocks on a rebuild: queries in
    flight keep reading vN until the vN+1 buffer swaps in atomically (or
    is discarded because another observation landed mid-build).
    """
    key = jax.random.PRNGKey(seed)
    kd, kq, ko = jax.random.split(key, 3)
    T = num_tasks if model == "multitask" else 0
    X, y = _toy(kd, n, d, T)
    gp = build_model(
        model, max_cg_iters=max_cg_iters, precision=precision, num_tasks=num_tasks
    )
    if fit_steps > 0:
        params, _ = gp.fit(X, y, steps=fit_steps)
    else:
        params = gp.init_params(X)
    session = PosteriorSession(gp, params, X, y, max_staleness=max_staleness)
    if session_hook is not None:
        session_hook(session)

    # warm the query path before opening the floodgates
    jax.block_until_ready(session.query(_query_batch(kq, batch, d, T))[0])

    latencies = []
    lat_lock = threading.Lock()

    def one_query(r):
        Xq = _query_batch(jax.random.fold_in(kq, r), batch, d, T)
        t0 = time.perf_counter()
        mean, _ = session.query(Xq)
        jax.block_until_ready(mean)
        dt = time.perf_counter() - t0
        with lat_lock:
            latencies.append(dt)

    refresh_futures = []
    t_start = time.perf_counter()
    with ThreadPoolExecutor(max_workers=threads) as pool, ThreadPoolExecutor(
        max_workers=1
    ) as refresher:
        query_futures = []
        for r in range(requests):
            query_futures.append(pool.submit(one_query, r))
            if observe_every and (r + 1) % observe_every == 0:
                Xn, yn = _observation(jax.random.fold_in(ko, r), observe_batch, d, T)
                path = session.observe(Xn, yn)
                # double-buffered refresh off the request path — but only
                # after an incremental append: when observe already fell
                # back to a full rebuild, the cache IS fresh and another
                # build would be pure duplicate work
                if path == "append":
                    refresh_futures.append(session.rebuild_async(refresher))
        for f in query_futures:
            f.result()
    wall = time.perf_counter() - t_start
    swaps = [f.result() for f in refresh_futures]
    swapped = sum(1 for s in swaps if s is not None)
    discarded = len(swaps) - swapped

    qps = requests * batch / wall
    metrics = {
        "model": f"serve_threaded_{model}",
        "n": n,
        "batch": batch,
        "requests": requests,
        "threads": threads,
        "concurrent_qps": qps,
        "query_ms_p50": sorted(latencies)[len(latencies) // 2] * 1e3,
        "async_refreshes_swapped": swapped,
        "async_refreshes_discarded": discarded,
        "final_n": session.n,
        "cache_version": session.cache_info.version,
        "cache_staleness": session.cache_info.staleness,
    }
    if verbose:
        print(
            f"[{model} x{threads} threads] n={n}→{session.n} | "
            f"{requests} x {batch}-pt queries: {qps:,.0f} pts/s concurrent "
            f"(p50 {metrics['query_ms_p50']:.1f} ms) | double-buffered "
            f"refreshes: {swapped} swapped, {discarded} discarded | "
            f"cache v{metrics['cache_version']}"
        )
    return metrics


def _inject_operator(op, schedule, negative_diag=0.0):
    """Thread a FaultInjectingOperator INSIDE the AddedDiag wrapper, so the
    engine's preconditioner dispatch still sees the K + σ²I structure it
    builds the pivoted-Cholesky factors from."""
    if isinstance(op, AddedDiagOperator):
        return AddedDiagOperator(
            FaultInjectingOperator(
                op.base, schedule=schedule, negative_diag=negative_diag
            ),
            op.sigma2,
        )
    return FaultInjectingOperator(
        op, schedule=schedule, negative_diag=negative_diag
    )


class _ChaosModel:
    """GPModel wrapper that injects faults at the operator seam.

    Delegates the whole protocol to the wrapped model and overrides only
    the engine-facing cache paths (``operator`` / ``posterior_cache`` /
    ``update_cache``) so every mBCG solve runs against a
    :class:`FaultInjectingOperator` driven by one shared live
    :class:`FaultSchedule` — the drill toggles the schedule mid-run and
    already-jitted solves feel it (the injection decision is a
    ``pure_callback``, made per execution, not per trace)."""

    def __init__(self, base, schedule, negative_diag=0.0):
        self._base = base
        self.schedule = schedule
        self.negative_diag = negative_diag

    def __getattr__(self, name):
        return getattr(self._base, name)

    def operator(self, params, data):
        return _inject_operator(
            self._base.operator(params, data), self.schedule, self.negative_diag
        )

    def posterior_cache(self, params, data, y, *, key=None, variance_cache=True):
        key = jax.random.PRNGKey(0) if key is None else key
        return build_posterior_cache(
            self.operator(params, data), y, key, self._base.settings,
            variance_cache=variance_cache,
        )

    def update_cache(self, params, data, y, cache, X_new, y_new):
        return extend_posterior_cache(
            self.operator(params, data), y, cache, self._base.settings
        )


def run_serve_chaos(
    *,
    n: int = 128,
    d: int = 2,
    batch: int = 64,
    requests_per_phase: int = 6,
    threads: int = 4,
    max_cg_iters: int = 40,
    nan_rate: float = 1.0,
    latency_s: float = 0.0,
    breaker_threshold: int = 2,
    breaker_reset_s: float = 0.3,
    seed: int = 0,
    verbose: bool = True,
    session_hook=None,
) -> dict:
    """The fault-injection drill: serve through injected faults, assert the
    robustness stack absorbed them.

    Four phases over one threaded :class:`PosteriorSession` (ExactGP,
    ``precision="mixed"``, ``on_failure="degrade"``):

      1. **clean** — build + serve, schedule inactive (health baseline);
      2. **nan** — ``nan_rate`` corrupts the *reduced-precision* matmuls
         only; a streamed ``observe`` forces a cache refresh whose solve
         goes unhealthy and the ladder's ``precision_f32`` rung heals it
         (≥1 recorded precision-escalation retry);
      3. **outage** — every matmul and ``to_dense`` goes NaN; a params
         nudge invalidates the cache, guarded rebuilds exhaust their
         retries, the breaker opens, and queries serve the last consistent
         cache flagged degraded (≥1 degraded query, zero raised queries);
      4. **recovery** — faults off, breaker cool-down elapses, the
         half-open trial rebuild succeeds and the breaker re-closes.

    Returns the metric row; ``chaos_ok`` is the CI gate (exit status).
    """
    key = jax.random.PRNGKey(seed)
    kd, kq, ko = jax.random.split(key, 3)
    X, y = _toy(kd, n, d)
    gp = build_model("exact", max_cg_iters=max_cg_iters, precision="mixed")
    gp.settings = dataclasses.replace(gp.settings, on_failure="degrade")
    params = gp.init_params(X)
    schedule = FaultSchedule(seed, reduced_only=True, latency_s=latency_s)
    chaos = _ChaosModel(gp, schedule)
    session = PosteriorSession(
        chaos, params, X, y,
        max_staleness=8,
        query_deadline_s=60.0,
        rebuild_retries=1,
        rebuild_backoff_s=0.01,
        breaker_threshold=breaker_threshold,
        breaker_reset_s=breaker_reset_s,
    )
    if session_hook is not None:
        session_hook(session)

    unhandled: list = []
    handled_failures: list = []
    latencies: list = []
    lat_lock = threading.Lock()

    def one_query(r):
        Xq = _query_batch(jax.random.fold_in(kq, r), batch, d)
        t0 = time.perf_counter()
        try:
            mean, _ = session.query(Xq)
            jax.block_until_ready(mean)
        except Exception as e:  # noqa: BLE001 — the drill counts, never hides
            with lat_lock:
                unhandled.append(repr(e))
            return
        with lat_lock:
            latencies.append(time.perf_counter() - t0)

    def fire_queries(pool, base, k=requests_per_phase):
        futures = [pool.submit(one_query, base + r) for r in range(k)]
        for f in futures:
            f.result()

    def esc_count():
        with session._lock:
            return sum(
                1
                for rep in session.health_reports
                for rung in rep.rungs
                if rung.rung == "precision_f32"
            )

    with warnings.catch_warnings():
        # degrade-path warnings are the EXPECTED signal here; count them
        # via the health reports instead of spamming the drill output
        warnings.simplefilter("ignore", SolveHealthWarning)
        t_start = time.perf_counter()
        with ThreadPoolExecutor(max_workers=threads) as pool:
            # phase 1: clean serving baseline
            jax.block_until_ready(session.query(_query_batch(kq, batch, d))[0])
            fire_queries(pool, 0)

            # phase 2: NaN in the reduced-precision matmuls; the streamed
            # observe refreshes the cache through the degradation ladder
            schedule.nan_rate = nan_rate
            Xn, yn = _observation(jax.random.fold_in(ko, 0), 1, d)
            try:
                session.observe(Xn, yn)
            except Exception as e:  # noqa: BLE001
                handled_failures.append(("observe_nan", repr(e)))
            fire_queries(pool, 100)
            escalations = esc_count()

            # phase 3: total outage — rebuilds cannot succeed at ANY rung
            schedule.nan_rate = 0.0
            schedule.total_outage = True
            session.update_params(
                jax.tree_util.tree_map(lambda p: p + 1e-6, session.params)
            )
            Xn, yn = _observation(jax.random.fold_in(ko, 1), 1, d)
            try:
                session.observe(Xn, yn)
            except Exception as e:  # noqa: BLE001
                handled_failures.append(("observe_outage", repr(e)))
            fire_queries(pool, 200)
            degraded_after_outage = session.degraded_queries
            breaker_opened = any(
                to == CircuitBreaker.OPEN
                for _, to, _ in session.breaker.transitions
            )

            # phase 4: recovery — faults off, cool-down, half-open trial
            schedule.total_outage = False
            time.sleep(breaker_reset_s + 0.05)
            fire_queries(pool, 300)
        wall = time.perf_counter() - t_start

    stats = session.health_stats()
    lat_sorted = sorted(latencies)
    total = len(latencies) + len(unhandled)
    metrics = {
        "model": "serve_chaos_exact",
        "n": n,
        "batch": batch,
        "threads": threads,
        "requests": total,
        "wall_s": wall,
        "query_ms_p50": (
            lat_sorted[len(lat_sorted) // 2] * 1e3 if lat_sorted else float("nan")
        ),
        "query_ms_p99": (
            lat_sorted[min(len(lat_sorted) - 1, int(len(lat_sorted) * 0.99))]
            * 1e3
            if lat_sorted
            else float("nan")
        ),
        "error_rate": len(unhandled) / total if total else 0.0,
        "unhandled_exceptions": len(unhandled),
        "handled_failures": len(handled_failures),
        "precision_escalations": escalations,
        "degraded_queries": stats["degraded_queries"],
        "rebuild_failures": stats["rebuild_failures"],
        "breaker_transitions": len(stats["breaker_transitions"]),
        "breaker_state": stats["breaker_state"],
        "fault_calls": schedule.calls,
        "fault_injected": len(schedule.injected),
    }
    metrics["chaos_ok"] = bool(
        not unhandled
        and escalations >= 1
        and degraded_after_outage >= 1
        and breaker_opened
        and stats["breaker_state"] == CircuitBreaker.CLOSED
    )
    if verbose:
        print(
            f"[chaos exact] {total} queries, {len(unhandled)} unhandled | "
            f"{escalations} precision escalation(s), "
            f"{stats['degraded_queries']} degraded quer"
            f"{'y' if stats['degraded_queries'] == 1 else 'ies'}, "
            f"{stats['rebuild_failures']} rebuild failure(s) | breaker "
            f"{'→'.join([CircuitBreaker.CLOSED] + [t for _, t, _ in stats['breaker_transitions']])} | "
            f"{schedule.calls} matmul calls, {len(schedule.injected)} injected | "
            f"p50 {metrics['query_ms_p50']:.1f} ms p99 {metrics['query_ms_p99']:.1f} ms | "
            f"{'OK' if metrics['chaos_ok'] else 'FAILED'}"
        )
        if unhandled:
            for e in unhandled[:5]:
                print(f"  unhandled: {e}")
    return metrics


def _health_payload(session) -> dict:
    """/health JSON: the session's health_stats() once one is serving."""
    if session is None:
        return {"status": "starting"}
    stats = session.health_stats()
    stats["status"] = "serving"
    return stats


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--model", default="sgpr", choices=list(MODELS))
    ap.add_argument("--n", type=int, default=1000)
    ap.add_argument("--d", type=int, default=2)
    ap.add_argument("--requests", type=int, default=20)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--observe-every", type=int, default=5,
                    help="observe a new point after every k-th request (0=never)")
    ap.add_argument("--observe-batch", type=int, default=1)
    ap.add_argument("--max-staleness", type=int, default=8)
    ap.add_argument("--fit-steps", type=int, default=0,
                    help="Adam steps before serving (0 = serve at init params)")
    ap.add_argument("--max-cg-iters", type=int, default=25)
    ap.add_argument("--precision", default=None, choices=[None, "highest", "mixed"])
    ap.add_argument("--num-tasks", type=int, default=2,
                    help="T for --model multitask (ignored otherwise)")
    ap.add_argument("--threads", type=int, default=0,
                    help="run the concurrent thread-pool driver with this "
                    "many query workers (0 = sequential driver)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the fault-injection drill over the threaded "
                    "driver (NaN injection -> ladder escalation -> outage -> "
                    "breaker -> recovery); exits nonzero unless the "
                    "robustness stack absorbed every fault")
    ap.add_argument("--chaos-nan-rate", type=float, default=1.0,
                    help="per-matmul NaN probability during the injection "
                    "phase (seeded; 1.0 = every reduced-precision call)")
    ap.add_argument("--chaos-latency", type=float, default=0.0,
                    help="artificial per-matmul host latency (seconds)")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve Prometheus /metrics + /health JSON on this "
                    "localhost port for the duration of the run (installs "
                    "the obs metrics registry; 0 = ephemeral port, printed "
                    "at startup)")
    ap.add_argument("--metrics-hold", type=float, default=0.0,
                    help="keep the metrics endpoint up this many seconds "
                    "after the run completes (lets a CI smoke test scrape a "
                    "finished drill before the process exits)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    configure_compile_cache()

    server = None
    holder: dict = {}
    hook = None
    if args.metrics_port is not None:
        if obs.active() is None:
            obs.install()
        server = obs.MetricsServer(
            port=args.metrics_port,
            health_fn=lambda: _health_payload(holder.get("session")),
        ).start()
        hook = lambda s: holder.__setitem__("session", s)  # noqa: E731
        print(f"[obs] metrics: {server.url}/metrics  health: {server.url}/health")
    try:
        if args.chaos:
            metrics = run_serve_chaos(
                n=args.n, d=args.d, batch=args.batch,
                threads=max(args.threads, 2), max_cg_iters=args.max_cg_iters,
                nan_rate=args.chaos_nan_rate, latency_s=args.chaos_latency,
                seed=args.seed, session_hook=hook,
            )
            if not metrics["chaos_ok"]:
                sys.exit(1)
            return metrics
        if args.threads > 0:
            return run_serve_threaded(
                model=args.model, n=args.n, d=args.d, requests=args.requests,
                batch=args.batch, observe_every=args.observe_every,
                observe_batch=args.observe_batch, max_staleness=args.max_staleness,
                fit_steps=args.fit_steps, max_cg_iters=args.max_cg_iters,
                precision=args.precision, num_tasks=args.num_tasks,
                threads=args.threads, seed=args.seed, session_hook=hook,
            )
        return run_serve(
            model=args.model, n=args.n, d=args.d, requests=args.requests,
            batch=args.batch, observe_every=args.observe_every,
            observe_batch=args.observe_batch, max_staleness=args.max_staleness,
            fit_steps=args.fit_steps, max_cg_iters=args.max_cg_iters,
            precision=args.precision, num_tasks=args.num_tasks, seed=args.seed,
            session_hook=hook,
        )
    finally:
        if server is not None:
            if args.metrics_hold > 0:
                print(
                    f"[obs] holding {server.url} for {args.metrics_hold:.0f}s "
                    "(scrape window)"
                )
                time.sleep(args.metrics_hold)
            server.stop()


if __name__ == "__main__":
    main()
