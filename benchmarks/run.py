"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines, writes JSON artifacts to
benchmarks/artifacts/, and maintains the machine-readable perf trajectory
file ``BENCH_speed.json`` at the repo root (n, wall-time, CG iterations,
speedup vs Cholesky, batched-vs-loop and cached-vs-uncached speedups) so
speed changes are tracked across PRs.

Roofline/dry-run numbers come from ``repro.launch.dryrun`` (they need 512
fake devices and live in their own process); everything here runs on the
plain CPU backend.  ``--fast`` trims problem sizes for CI-budget runs.
"""

import argparse
import json
import os
import platform
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only",
        default=None,
        help="comma-separated subset: solve_error,speed,mae,preconditioner,"
        "complexity,serve,fused,multitask,health,million",
    )
    ap.add_argument(
        "--scenario",
        default=None,
        help="alias for --only (e.g. --scenario serve: PosteriorSession "
        "cached-QPS and append-vs-rebuild rows; --scenario fused: per-"
        "iteration time, launch count and HBM bytes of the fused CG step; "
        "--scenario multitask: Kronecker BBMM vs naive dense nT×nT rows "
        "for T in {2, 4, 8}; --scenario million: partitioned-MVM exact-GP "
        "solves at n up to 1e5 with per-panel timing, the n=1e6 roofline "
        "extrapolation and the BBMM-vs-Cholesky crossover — "
        "MILLION_SIZES=20000 env var trims the grid for smoke runs)",
    )
    ap.add_argument(
        "--fast",
        action="store_true",
        help="trimmed problem sizes (CI budget); affects the speed suite",
    )
    ap.add_argument(
        "--dtype",
        choices=["float32", "bfloat16"],
        default="float32",
        help="compute dtype for the speed suite's engine rows: bfloat16 runs "
        "them at precision='mixed' (bf16 kernel tiles, f32 accumulation, "
        "periodic f32 residual refresh); the mixed-vs-highest tolerance row "
        "is recorded either way",
    )
    args = ap.parse_args()
    only = args.only or args.scenario

    from repro.launch.compile_cache import configure_compile_cache

    configure_compile_cache()

    from . import (
        complexity,
        fused,
        health,
        mae,
        million,
        multitask,
        preconditioner,
        serve,
        solve_error,
        speed,
    )

    suites = {
        "solve_error": solve_error.run,  # paper Fig 1
        "preconditioner": preconditioner.run,  # paper Fig 4
        "complexity": complexity.run,  # paper §4/§5 claims
        "speed": speed.run,  # paper Fig 2 + batched/cache levers
        "mae": mae.run,  # paper Fig 3
        "serve": serve.run,  # PosteriorSession QPS + append-vs-rebuild
        "fused": fused.run,  # fused CG step: launches/iter + HBM bytes/iter
        "multitask": multitask.run,  # Kronecker BBMM vs naive dense nT×nT
        "health": health.run,  # health-check overhead (~0) + chaos-drill p50/p99
        "million": million.run,  # partitioned MVMs: n≤1e5 solves + 1e6 roofline
    }
    wanted = only.split(",") if only else list(suites)

    print("name,us_per_call,derived")
    t0 = time.time()
    speed_rows = []  # rows from the perf-trajectory suites (speed, serve)
    for name in wanted:
        print(f"# --- {name} ---", flush=True)
        if name == "speed":
            speed_rows += suites[name](fast=args.fast, dtype=args.dtype)
        elif name in ("serve", "fused", "multitask", "health", "million"):
            speed_rows += suites[name](fast=args.fast)
        else:
            suites[name]()
    if speed_rows:
        _write_bench_speed(speed_rows, fast=args.fast)
    print(f"# total {time.time()-t0:.1f}s", flush=True)


def _write_bench_speed(rows, *, fast: bool) -> None:
    """BENCH_speed.json at the repo root: the cross-PR perf trajectory."""
    import jax

    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "BENCH_speed.json")
    payload = {
        "schema": 1,
        "fast_mode": fast,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "python": platform.python_version(),
        "jax": jax.__version__,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "rows": rows,
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=str)
    print(f"# wrote {path}", flush=True)


if __name__ == "__main__":
    main()
