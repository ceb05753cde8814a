"""repro.obs — dependency-free telemetry for solver → engine → serving.

Three pieces, one discipline:

* :mod:`repro.obs.registry` — process-wide metrics registry (counters,
  gauges, fixed-log-bucket histograms; thread-safe, label-keyed).
* :mod:`repro.obs.trace` — per-solve host spans emitting Chrome
  trace-event JSON (Perfetto-loadable), each also a
  ``jax.profiler.TraceAnnotation`` on the device trace's clock.
* :mod:`repro.obs.exposition` — Prometheus ``/metrics`` + ``/health``
  JSON on a stdlib ``http.server`` daemon thread, and the text-format
  parser behind the ``gp_top`` CLI.

The discipline: every seam in the instrumented code is a no-op unless a
sink is installed (``install()`` for metrics, ``trace()`` for spans) —
the same null-sink rule as ``health.collect()``, measured as
``obs_overhead_frac`` in ``benchmarks/health.py``.
"""

from .registry import (  # noqa: F401
    DEFAULT_BUCKETS,
    MetricsRegistry,
    active,
    inc,
    install,
    installed,
    observe,
    set_gauge,
    uninstall,
)
from .trace import (  # noqa: F401
    TraceCollector,
    active_trace,
    instant,
    span,
    trace,
)
from .exposition import MetricsServer, parse_prometheus  # noqa: F401

__all__ = [
    "DEFAULT_BUCKETS",
    "MetricsRegistry",
    "MetricsServer",
    "TraceCollector",
    "active",
    "active_trace",
    "inc",
    "install",
    "installed",
    "instant",
    "observe",
    "parse_prometheus",
    "set_gauge",
    "span",
    "trace",
    "uninstall",
]
