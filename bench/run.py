#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print one JSON result line.

    python3 bench/run.py --workload elevators.train --seed 7 --seconds 51 --trace 0

The cell, its configuration, traffic mix and per-layer metrics are found
by name from ``BENCHMARK.json`` (see ``bench/spec.py``).  ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` traces the window and
reports its per-layer metrics, with the device's busy time and a breakdown.
Either way the window's answers are checked against the plain reference
after it closes; the numbers compared are printed with their limits as
the last lines of stderr and under ``checks`` in the result.

There is no CPU fallback: without a TPU, with fewer chips than the cell
asks for, or with a device kind missing from ``bench/peaks.json``, the run
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # set-up is measured from process start

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.common import Run  # noqa: E402
from bench.spec import Spec, SpecError  # noqa: E402

OUT = os.path.join("bench", "out")  # traces, under the root (ignored by git)


class NoChip(RuntimeError):
    """The machine lacks what the cell needs."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def find_devices(chips: int, peaks: dict):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform={devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
    if devs[0].device_kind not in peaks:
        raise NoChip(f"device kind {devs[0].device_kind!r} is not in bench/peaks.json")
    return devs[:chips]


def compile_cache() -> str:
    """The program's persistent compile cache (``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names another), keeping every
    program so a cell's second run compiles nothing."""
    import jax

    from repro.launch.compile_cache import configure_compile_cache

    path = configure_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def per_layer(spec: Spec, cell: str, ctx: dict) -> dict:
    out = {}
    for m in spec.per_layer(cell):
        f = spec.metric(m["name"])
        reducer = importlib.import_module(f"bench.reducers.{f['reducer']}")
        value = reducer.reduce(ctx, **f.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, *, require_chip: bool = True, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be non-negative")

    try:
        spec = Spec(root)
        cell = spec.cell(args.workload)
        config = spec.config(cell["config"])
        traffic = spec.traffic(cell["traffic"])
        driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
        peaks_all = spec.peaks()
        import jax

        devs = find_devices(cell["chips"], peaks_all) if require_chip else jax.devices()[:1]
    except (SpecError, NoChip) as e:
        log(f"FAILED: {e}")
        return 2
    peaks = peaks_all.get(devs[0].device_kind, {})
    cache = compile_cache()
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(root, OUT, f"{args.workload}.trace")
        shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"[run] workload={args.workload} seed={args.seed} seconds={args.seconds} "
        f"trace={args.trace} device={devs[0].device_kind} x{len(devs)} cache={cache}")

    r = Run(cell=cell, config=config, traffic=traffic, seed=args.seed,
            seconds=args.seconds, t0=T0, trace_dir=trace_dir, log=log)
    out = driver.run(r)

    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": out.memory_peak_bytes}
    result = {}
    if args.trace:
        from bench.trace import Trace

        tr = Trace.from_dir(trace_dir)
        ctx = dict(out.layer, trace=tr, peaks=peaks, chips=len(devs))
        metrics = per_layer(spec, args.workload, ctx)
        device.update(busy_s=tr.busy_s(), window_s=tr.window_s())
        result["breakdown"] = {"device_ops": tr.op_seconds(), "idle_gaps": tr.idle_gaps()}
        tr.to_json(os.path.join(root, OUT, f"{args.workload}.events.json.gz"))
    else:
        values = dict(out.e2e, setup_s=out.setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end(args.workload)}
    correct = bool(out.checks) and all(c.ok for c in out.checks)
    checks = {c.name: {"value": _json_number(c.value), "limit": c.limit} for c in out.checks}
    for c in out.checks:
        log(f"[check] {c.name} = {c.value!r} limit {c.limit!r} {'ok' if c.ok else 'FAIL'}")
    line = {"correct": correct, "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device, **result, "checks": checks}
    print(json.dumps(line, allow_nan=False), flush=True)
    return 0


def _json_number(x: float):
    """A non-finite reading as its name: JSON has no inf or nan."""
    return x if math.isfinite(x) else repr(x)


if __name__ == "__main__":
    sys.exit(main())
