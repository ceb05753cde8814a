"""PosteriorSession — the versioned serving wrapper over any GPModel.

The session owns the serving triple (params, X, y) and a posterior cache
derived from it, and keeps the two consistent through an explicit
version/fingerprint discipline:

  * every live cache carries a :class:`CacheInfo` — a monotonically
    increasing version number, the SHA-1 **fingerprint** of the exact
    (params, X, y) it was derived from, and its *staleness* (number of
    incremental updates since the last full build);
  * every mutation of the serving state goes through the session API
    (``observe`` appends data, ``update_params`` swaps hyperparameters),
    which re-fingerprints the state — a cache whose fingerprint no longer
    matches is invalid and is rebuilt before the next query is answered;
  * ``observe(X_new, y_new)`` keeps the cache live *incrementally* when
    the model supports streaming (``update_cache``): an exact rank-k
    Woodbury refresh for SGPR/BLR (O(m³), zero CG solves), warm-started
    CG with Krylov-basis recycling for ExactGP/DKL.  Once
    ``max_staleness`` consecutive incremental updates have accumulated —
    or the model has no streaming path (SKI) — it falls back to a full
    rebuild;
  * ``stale()`` / ``rebuild()`` are the async-refresh hooks: a background
    refresher polls ``stale()`` (or just ``staleness > 0``) and calls
    ``rebuild()`` off the request path; the cache+info swap is atomic
    under the session lock, so concurrent ``query`` calls always see a
    consistent (cache, fingerprint) pair;
  * ``rebuild_async(executor)`` is the **double-buffered** variant: vN
    keeps serving while vN+1 builds on a worker, and the finished buffer
    swaps in only on fingerprint match (a mutation that landed mid-build
    invalidates the buffer, which is discarded) — the thread-pool request
    driver in ``repro.launch.gp_serve`` exercises it under concurrent
    query traffic.

Queries (``query``) are served entirely from the cache — zero CG
iterations for every model (guarded by tests/test_serving.py).
"""

from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import health
from repro.gp.model import missing_protocol_methods, supports_streaming


def fingerprint(tree) -> str:
    """SHA-1 content fingerprint of an arbitrary pytree of arrays.

    Hashes every leaf's shape, dtype and raw bytes (host transfer — this
    is a mutation-time cost, never a query-time one)."""
    h = hashlib.sha1()
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = np.asarray(leaf)
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


@dataclasses.dataclass(frozen=True)
class CacheInfo:
    """Provenance of a live posterior cache."""

    version: int  # bumped on every cache swap (build or incremental)
    fingerprint: str  # of the (params, X, y) this cache serves
    n: int  # training rows covered
    staleness: int  # incremental updates since the last full build
    degraded: bool = False  # True while queries are being answered from the
    # last CONSISTENT cache instead of a current one — the circuit breaker
    # is open (consecutive rebuild failures) and fresh mutations are not yet
    # reflected in served posteriors.  Cleared by the next successful swap.


class QueryDeadlineExceeded(TimeoutError):
    """A query could not be admitted within its per-query deadline."""


class RebuildFailed(RuntimeError):
    """No cache could be (re)built and no consistent fallback exists."""


class CircuitBreaker:
    """Per-session circuit breaker over posterior-cache rebuilds.

    Classic three-state machine, deterministic via an injectable clock:

      * ``closed``    — rebuilds flow normally; failures count up;
      * ``open``      — ``threshold`` consecutive failures tripped it; no
        rebuild is attempted until ``reset_after_s`` has elapsed (queries
        serve the last consistent cache, flagged degraded);
      * ``half_open`` — the cool-down elapsed; ONE trial rebuild is
        admitted — success re-closes, failure re-opens.

    ``transitions`` records the most recent (from, to, t) edges — the
    assertion surface for deterministic breaker tests.  It is a ring buffer
    (``transition_history`` entries) so a long-lived session cannot grow it
    unboundedly; ``transitions_total`` counts every edge ever taken (also
    exported as the ``breaker_transitions_total`` registry counter).
    """

    CLOSED = "closed"
    OPEN = "open"
    HALF_OPEN = "half_open"

    def __init__(
        self,
        threshold: int = 3,
        reset_after_s: float = 30.0,
        *,
        clock=time.monotonic,
        transition_history: int = 64,
    ):
        self.threshold = int(threshold)
        self.reset_after_s = float(reset_after_s)
        self._clock = clock
        self._lock = threading.Lock()
        self.state = self.CLOSED
        self.failures = 0
        self._opened_at: float | None = None
        self.transitions: deque = deque(maxlen=int(transition_history))
        self.transitions_total = 0

    def _set(self, state: str) -> None:
        if state != self.state:
            self.transitions.append((self.state, state, self._clock()))
            self.transitions_total += 1
            obs.inc(
                "breaker_transitions_total",
                **{"from": self.state, "to": state},
            )
            self.state = state

    def allow(self) -> bool:
        """May a rebuild be attempted right now?"""
        with self._lock:
            if self.state == self.OPEN:
                if self._clock() - self._opened_at >= self.reset_after_s:
                    self._set(self.HALF_OPEN)
                    return True
                return False
            return True  # closed, or half-open trial

    def record_success(self) -> None:
        with self._lock:
            self.failures = 0
            self._set(self.CLOSED)

    def record_failure(self) -> None:
        with self._lock:
            self.failures += 1
            if self.state == self.HALF_OPEN or self.failures >= self.threshold:
                self._set(self.OPEN)
                self._opened_at = self._clock()


def _require_finite(name: str, arr) -> None:
    bad = int(jax.device_get(jnp.sum(~jnp.isfinite(arr))))
    if bad:
        raise ValueError(
            f"{name} contains {bad} non-finite value(s) (NaN/Inf) out of "
            f"{arr.size}; clean the rows (e.g. drop or impute them) before "
            "conditioning a posterior on them — a single non-finite entry "
            "poisons every solve"
        )


class PosteriorSession:
    """Versioned, streaming-updatable posterior serving for one GP model.

    Args:
      model: any :class:`repro.gp.model.GPModel`.
      params: fitted hyperparameters.
      X, y: training data the posterior conditions on.
      max_staleness: how many consecutive incremental ``observe`` updates
        may accumulate before the next one forces a full rebuild
        (0 → streaming disabled, every observe rebuilds).  Woodbury
        updates are algebraically exact, so for SGPR/BLR this bounds only
        floating-point accumulation; for the Krylov caches it also bounds
        basis growth (≤ max_cg_iters+1 columns per update) — and the
        model's ``settings.max_basis_columns`` bounds it *in memory*
        instead: streamed bases past that budget are Rayleigh–Ritz
        compacted (conservative variances at fixed memory; see
        ``repro.core.inference.extend_posterior_cache``).
      build: build the cache eagerly (default) or lazily on first query.
      query_deadline_s: per-query admission deadline — a query that cannot
        obtain a servable cache (it is waiting on another worker's rebuild)
        within this budget serves the last consistent cache degraded, or
        raises :class:`QueryDeadlineExceeded` if none exists.  None (default)
        waits indefinitely.  The deadline governs admission, not the jax
        compute itself (which cannot be preempted).
      rebuild_retries / rebuild_backoff_s: failed cache rebuilds are retried
        up to ``rebuild_retries`` more times with exponential backoff
        (``rebuild_backoff_s``·2^attempt between attempts) before counting
        as a rebuild failure.
      breaker_threshold / breaker_reset_s: consecutive rebuild failures
        (post-retry) before the per-session :class:`CircuitBreaker` opens,
        and its cool-down before a half-open trial.  While open, queries
        are answered from the last consistent cache with
        ``cache_info.degraded=True`` instead of erroring the request path.
      clock / sleep: injectable time sources (deterministic tests).
    """

    def __init__(
        self,
        model,
        params,
        X,
        y,
        *,
        max_staleness: int = 8,
        build: bool = True,
        query_deadline_s: float | None = None,
        rebuild_retries: int = 2,
        rebuild_backoff_s: float = 0.05,
        breaker_threshold: int = 3,
        breaker_reset_s: float = 30.0,
        clock=time.monotonic,
        sleep=time.sleep,
    ):
        missing = missing_protocol_methods(model)
        if missing:
            raise TypeError(
                f"{type(model).__name__} does not implement the GPModel "
                f"protocol (missing: {missing})"
            )
        self.model = model
        self.max_staleness = int(max_staleness)
        self.query_deadline_s = query_deadline_s
        self.rebuild_retries = int(rebuild_retries)
        self.rebuild_backoff_s = float(rebuild_backoff_s)
        self._clock = clock
        self._sleep = sleep
        self.breaker = CircuitBreaker(
            breaker_threshold, breaker_reset_s, clock=clock
        )
        # observability: solve-health reports from builds/updates (bounded),
        # and the serving-degradation counters the chaos harness asserts on
        self.health_reports: deque = deque(maxlen=256)
        self.degraded_queries = 0
        self.rebuild_failures = 0
        self._lock = threading.RLock()
        # single-flight gate for lazy rebuilds: N query workers hitting a
        # stale cache run ONE build (the rest wait for the swap), not N
        self._rebuild_gate = threading.Lock()
        # the last internally-consistent (params, data, cache) triple —
        # what queries serve while an incremental append is in flight
        # (state fingerprint already moved, refreshed cache not swapped yet)
        self._serving = None
        self._appends_in_flight = 0
        self._params = params
        self._X = jnp.atleast_2d(jnp.asarray(X))
        self._y = jnp.atleast_1d(jnp.asarray(y))
        _require_finite("X", self._X)
        _require_finite("y", self._y)
        self._data = model.prepare_inputs(self._X)
        self._state_fp = fingerprint((self._params, self._X, self._y))
        self._cache = None
        self._info: CacheInfo | None = None
        self._version = 0
        if build:
            self.rebuild()

    # -- state accessors ----------------------------------------------------
    @property
    def params(self):
        return self._params

    @property
    def X(self):
        return self._X

    @property
    def y(self):
        return self._y

    @property
    def n(self) -> int:
        return int(self._y.shape[0])

    @property
    def cache(self):
        """The live posterior cache pytree (None before the first build) —
        read-only; callers wanting sync semantics can
        ``jax.block_until_ready(jax.tree_util.tree_leaves(session.cache))``."""
        return self._cache

    @property
    def cache_info(self) -> CacheInfo | None:
        """Provenance of the live cache (None before the first build)."""
        return self._info

    @property
    def streaming(self) -> bool:
        return supports_streaming(self.model) and self.max_staleness > 0

    # -- versioning / refresh hooks ----------------------------------------
    def stale(self) -> bool:
        """True when the live cache no longer matches (params, X, y) —
        missing, or fingerprint drift (e.g. ``update_params`` happened and
        no rebuild ran yet).  Incremental ``observe`` updates re-stamp the
        cache fingerprint, so a successfully streamed cache is NOT stale;
        its ``cache_info.staleness`` counts how far it has drifted from a
        fresh build (the async-refresh signal)."""
        with self._lock:
            return self._cache is None or self._info.fingerprint != self._state_fp

    def _build_and_swap(self, params, data, y, fp) -> CacheInfo | None:
        """Build a cache for the snapshotted state and swap it in atomically
        — but only while the fingerprint still matches (or nothing is live
        yet): a mutation that landed mid-build must not be clobbered by the
        now-stale buffer.  Returns the swapped CacheInfo, or None when the
        buffer was discarded."""
        with health.collect() as reports, obs.span("serving:cache_build"):
            cache = self.model.posterior_cache(params, data, y)
        with self._lock:
            self.health_reports.extend(reports)
            if self._state_fp != fp and self._cache is not None:
                obs.inc("cache_swap_discards_total", kind="build")
                return None  # state moved on mid-build: discard buffer
            self._version += 1
            self._cache = cache
            self._serving = (params, data, cache)
            self._info = CacheInfo(
                version=self._version, fingerprint=fp,
                n=int(y.shape[0]), staleness=0,
            )
            obs.inc("cache_swaps_total", kind="build")
            return self._info

    def rebuild(self) -> CacheInfo:
        """Full posterior-cache build from the current (params, X, y).

        This is the async-refresh hook: it can run on a background worker
        (it only *reads* serving state until the final atomic swap), while
        queries keep being served from the previous cache.  Like
        ``rebuild_async``, the swap is fingerprint-gated: if a mutation
        landed mid-build, the stale buffer is discarded (the live — newer —
        cache and its info are returned instead of being clobbered)."""
        with self._lock:
            params, data, y, fp = self._params, self._data, self._y, self._state_fp
        info = self._build_and_swap(params, data, y, fp)
        if info is not None:
            return info
        with self._lock:
            return self._info

    def _rebuild_guarded(self) -> CacheInfo | None:
        """``rebuild`` with bounded exponential-backoff retry + breaker
        accounting: the request-path (and observe-path) rebuild entry.

        Returns the swapped CacheInfo, or raises the final attempt's error
        after recording a (post-retry) rebuild failure with the breaker.
        """
        last_err = None
        for attempt in range(1 + self.rebuild_retries):
            if attempt:
                self._sleep(self.rebuild_backoff_s * (2 ** (attempt - 1)))
            try:
                info = self.rebuild()
            except Exception as e:  # noqa: BLE001 — any build fault degrades
                last_err = e
                continue
            self.breaker.record_success()
            return info
        self.breaker.record_failure()
        with self._lock:
            self.rebuild_failures += 1
        obs.inc("rebuild_failures_total")
        raise last_err

    def refresh_if_stale(self) -> bool:
        """Poll-style hook for a background refresher: rebuild when the
        cache is invalid OR has accumulated incremental updates."""
        with self._lock:
            needs = self.stale() or (self._info is not None and self._info.staleness > 0)
        if needs:
            self.rebuild()
        return needs

    def rebuild_async(self, executor=None):
        """Double-buffered refresh: build vN+1 on a worker while vN serves.

        Snapshots the serving state under the lock, builds the next cache
        entirely OFF the request path (queries keep hitting the previous
        cache — ``query`` never blocks on the build), then swaps it in
        atomically **only if the state fingerprint still matches** the
        snapshot.  If a mutation (``observe`` / ``update_params``) landed
        while the build was in flight, the now-stale buffer is discarded
        (returns None) instead of clobbering the newer state — the caller
        just schedules another refresh.

        ``executor``: a ``concurrent.futures.Executor`` to run the build
        on (returns a Future resolving to the swapped :class:`CacheInfo`
        or None); None runs the build inline (returns the result
        directly) — handy for tests and single-threaded drivers.
        """
        with self._lock:
            params, data, y, fp = self._params, self._data, self._y, self._state_fp

        def _build():
            return self._build_and_swap(params, data, y, fp)

        if executor is None:
            return _build()
        return executor.submit(_build)

    # -- mutations ----------------------------------------------------------
    def update_params(self, params) -> None:
        """Swap hyperparameters.  Invalidates the cache (fingerprint
        mismatch); the rebuild happens lazily on the next query, or
        explicitly via ``rebuild()`` (async refresh)."""
        with self._lock:
            self._params = params
            self._state_fp = fingerprint((self._params, self._X, self._y))

    def observe(self, X_new, y_new) -> str:
        """Append observations (X_new, y_new) to the posterior.

        Returns the path taken: ``"append"`` (incremental cache update —
        exact rank-k Woodbury refresh or Krylov-recycled warm-started CG)
        or ``"rebuild"`` (full build: non-streaming model, no valid cache,
        or the ``max_staleness`` budget was exhausted).

        The appended state is derived and **validated before it is
        installed** (``prepare_inputs`` on the concatenated panel runs
        first — a rejected append, e.g. an out-of-range multitask task id,
        raises and leaves the session exactly as it was), and the
        incremental ``update_cache`` solve runs **off the session lock**,
        so concurrent ``query`` workers keep serving the previous cache
        during the append; the refreshed cache swaps in fingerprint-gated,
        like ``rebuild_async`` (a mutation racing in mid-update leaves the
        session stale rather than clobbered — the next query rebuilds).
        """
        if obs.active() is None and obs.active_trace() is None:
            return self._observe_impl(X_new, y_new)
        t0 = time.perf_counter()
        with obs.span("serving:observe"):
            try:
                path = self._observe_impl(X_new, y_new)
            except Exception:
                obs.inc("serving_observes_total", path="error")
                raise
        obs.inc("serving_observes_total", path=path)
        obs.observe("serving_observe_seconds", time.perf_counter() - t0, path=path)
        return path

    def _observe_impl(self, X_new, y_new) -> str:
        X_new = jnp.atleast_2d(jnp.asarray(X_new))
        y_new = jnp.atleast_1d(jnp.asarray(y_new))
        if X_new.shape[0] != y_new.shape[0]:
            raise ValueError(
                f"X_new rows ({X_new.shape[0]}) != y_new length ({y_new.shape[0]})"
            )
        # reject non-finite appends BEFORE any mutation: the session keeps
        # serving its current posterior exactly as if the call never happened
        _require_finite("X_new", X_new)
        _require_finite("y_new", y_new)
        with self._lock:
            X_full = jnp.concatenate([self._X, X_new], axis=0)
            y_full = jnp.concatenate([self._y, y_new], axis=0)
            # derive/validate BEFORE mutating: if the model rejects the
            # appended panel, the session state is untouched
            data = self.model.prepare_inputs(X_full)
            can_stream = (
                self.streaming
                and self._cache is not None
                and self._info.fingerprint == self._state_fp
                and self._info.staleness < self.max_staleness
            )
            params, cache = self._params, self._cache
            staleness = self._info.staleness if self._info is not None else 0
            self._X, self._y, self._data = X_full, y_full, data
            fp = fingerprint((params, X_full, y_full))
            self._state_fp = fp
            if can_stream:
                v0 = self._version
                self._appends_in_flight += 1
        if not can_stream:
            self._rebuild_guarded()
            return "rebuild"
        try:
            try:
                with health.collect() as reports:
                    new_cache = self.model.update_cache(
                        params, data, y_full, cache, X_new, y_new
                    )
            except Exception:
                # the data IS installed (validated above) but the cache is
                # now stale — the next query rebuilds.  Count the failure
                # with the breaker so a persistently failing update path
                # degrades instead of hammering
                self.breaker.record_failure()
                with self._lock:
                    self.rebuild_failures += 1
                obs.inc("rebuild_failures_total")
                raise
            with self._lock:
                self.health_reports.extend(reports)
                # discard if another mutation landed (fingerprint) or any
                # other build already swapped a cache in (version) — never
                # clobber a fresher full build with this incremental one
                if self._state_fp == fp and self._version == v0:
                    self._version += 1
                    self._cache = new_cache
                    self._serving = (params, data, new_cache)
                    self._info = CacheInfo(
                        version=self._version, fingerprint=fp,
                        n=int(y_full.shape[0]), staleness=staleness + 1,
                    )
                    obs.inc("cache_swaps_total", kind="append")
                else:
                    obs.inc("cache_swap_discards_total", kind="append")
        finally:
            with self._lock:
                self._appends_in_flight -= 1
        return "append"

    # -- queries ------------------------------------------------------------
    def _snapshot_consistent(self):
        """The (params, data, cache) triple a query may serve non-degraded,
        or None when a rebuild is needed first."""
        with self._lock:
            if self._cache is not None and self._info.fingerprint == self._state_fp:
                return self._params, self._data, self._cache
            # an incremental append is computing its refreshed cache
            # off-lock: serve the PREVIOUS consistent triple instead of
            # stalling on — or duplicating — the in-progress update
            if self._appends_in_flight > 0 and self._serving is not None:
                return self._serving
            return None

    def _serve_degraded(self):
        """Snapshot the last consistent triple for a degraded answer (or
        None if nothing was ever consistent), flagging ``cache_info``."""
        with self._lock:
            if self._serving is None:
                return None
            self.degraded_queries += 1
            obs.inc("serving_degraded_total")
            if self._info is not None and not self._info.degraded:
                self._info = dataclasses.replace(self._info, degraded=True)
            return self._serving

    def query(self, Xstar, **kwargs):
        """Posterior (mean, variance) at Xstar, served from the cache —
        zero CG iterations.  Rebuilds first if the cache is stale —
        single-flight under concurrency: when many query workers see the
        same stale cache, one runs the build (with retry/backoff via
        ``_rebuild_guarded``) and the rest wait for the swap instead of
        launching duplicates.  The (params, data, cache) snapshot is taken
        only when cache and state fingerprints agree under the lock, so a
        mutation racing in between observe's state update and its rebuild
        can never pair new data with an old cache; while an incremental
        append is in flight, queries serve the previous consistent
        (params, data, cache) triple instead.

        Hardened request path: when the circuit breaker is open (or a
        guarded rebuild just exhausted its retries), the query is answered
        from the LAST CONSISTENT triple with ``cache_info.degraded=True``
        instead of erroring — stale-but-finite beats unavailable for a
        serving posterior.  :class:`RebuildFailed` is raised only when no
        consistent cache has ever existed.  ``query_deadline_s`` bounds how
        long admission may wait on another worker's in-flight rebuild
        (:class:`QueryDeadlineExceeded` when nothing is servable in time).
        """
        if obs.active() is None and obs.active_trace() is None:
            return self._query_impl(Xstar, **kwargs)
        t0 = time.perf_counter()
        d0 = self.degraded_queries
        with obs.span("serving:query"):
            try:
                # the latency sample and the span end at ready answers, not
                # at the enqueue of an asynchronously dispatched computation
                out = jax.block_until_ready(self._query_impl(Xstar, **kwargs))
            except Exception:
                obs.inc("serving_queries_total", result="error")
                raise
        # per-call degradation inferred from the counter delta — exact
        # single-threaded; under contention a neighbour's degraded serve can
        # only OVER-count "degraded", never hide one
        result = "degraded" if self.degraded_queries > d0 else "ok"
        obs.inc("serving_queries_total", result=result)
        obs.observe("serving_query_seconds", time.perf_counter() - t0, result=result)
        return out

    def _query_impl(self, Xstar, **kwargs):
        deadline = (
            None
            if self.query_deadline_s is None
            else self._clock() + self.query_deadline_s
        )
        while True:
            triple = self._snapshot_consistent()
            if triple is not None:
                break
            # a rebuild is needed: breaker-gated, deadline-bounded
            if not self.breaker.allow():
                triple = self._serve_degraded()
                if triple is not None:
                    break
                raise RebuildFailed(
                    "circuit breaker is open and no consistent cache was "
                    "ever built for this session"
                )
            if deadline is not None:
                remaining = deadline - self._clock()
                acquired = remaining > 0 and self._rebuild_gate.acquire(
                    timeout=remaining
                )
                if not acquired:
                    triple = self._serve_degraded()
                    if triple is not None:
                        break
                    raise QueryDeadlineExceeded(
                        f"query could not be admitted within "
                        f"{self.query_deadline_s}s (rebuild in flight)"
                    )
            else:
                self._rebuild_gate.acquire()
            try:
                if self.stale():  # may have been rebuilt while we waited
                    try:
                        self._rebuild_guarded()
                    except Exception as e:
                        triple = self._serve_degraded()
                        if triple is not None:
                            break
                        raise RebuildFailed(
                            "posterior cache rebuild failed and no "
                            "consistent cache exists to degrade to"
                        ) from e
            finally:
                self._rebuild_gate.release()
        params, data, cache = triple
        return self.model.predict_cached(
            params, data, cache, jnp.asarray(Xstar), **kwargs
        )

    def health_stats(self) -> dict:
        """Operational counters + solve-health tallies for dashboards/tests.

        This is the structured-health-export surface (ROADMAP robustness
        frontier (d)): ``gp_serve --metrics-port`` serves it verbatim as
        ``/health`` JSON, and when a metrics registry is installed the same
        events also stream into label-keyed ``serving_*`` / ``cache_*`` /
        ``breaker_*`` series on ``/metrics`` — the dict view is the
        point-in-time summary, the registry view the scrapeable history
        (its serving-relevant families ride along under ``"registry"``)."""
        with self._lock:
            by_status: dict = {}
            for r in self.health_reports:
                by_status[r.status] = by_status.get(r.status, 0) + 1
            stats = {
                "breaker_state": self.breaker.state,
                "breaker_failures": self.breaker.failures,
                "breaker_transitions": list(self.breaker.transitions),
                "breaker_transitions_total": self.breaker.transitions_total,
                "degraded_queries": self.degraded_queries,
                "rebuild_failures": self.rebuild_failures,
                "reports_by_status": by_status,
                "degraded_rungs": sum(
                    1 for r in self.health_reports if r.degraded
                ),
            }
        reg = obs.active()
        if reg is not None:
            snap = reg.snapshot()
            stats["registry"] = {
                name: fam
                for name, fam in snap.items()
                if name.startswith(("serving_", "cache_", "breaker_", "solves_"))
            }
        return stats
