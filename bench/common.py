"""What every driver shares: the run's inputs, its outcome, and small
helpers for keys, device memory and the comparisons."""

from __future__ import annotations

import dataclasses
import math
import statistics
import sys
import time
from typing import Callable


@dataclasses.dataclass
class Run:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    t0: float  # perf_counter() at process start: set-up is measured from here
    trace_dir: str | None = None  # trace the window into this directory
    precision: str | None = None  # run the program at another precision
    log: Callable[[str], None] = lambda s: print(s, file=sys.stderr, flush=True)

    @property
    def tracing(self) -> bool:
        return self.trace_dir is not None


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    setup_s: float
    e2e: dict  # end-to-end metric name -> value
    attempted: int
    failed: int
    checks: list  # [Check]: the readings that have a limit
    readings: dict  # every number compared or read, by name
    layer: dict  # what the per-layer reducers read
    memory_peak_bytes: int


def now() -> float:
    return time.perf_counter()


def seed_key(seed: int):
    """A PRNG key from any non-negative seed, 64 bits and more."""
    import jax

    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def memory_peak_bytes(devices) -> int:
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use", 0) for dev in devices]
    return int(max(peaks, default=0))


def checks_of(readings: dict, limits: dict, log) -> list:
    """A Check for each reading that has a limit; the others are logged as
    read but not compared (a number no fault or control separates)."""
    for k, v in readings.items():
        if k not in limits:
            log(f"[reading] {k} = {v!r} (not compared)")
    return [Check(k, v, limits[k]) for k, v in readings.items() if k in limits]


def leaf_gaps(prog: dict, ref: dict, keep=None) -> dict:
    """Per leaf, | |prog_leaf| - |ref_leaf| | over max(|ref_leaf|, the
    median leaf's |ref|).  ``keep`` names the leaves compared (all if None)."""
    import numpy as np

    names = sorted(ref if keep is None else keep)
    rn = {k: float(np.linalg.norm(np.asarray(ref[k], np.float64))) for k in ref}
    pn = {k: float(np.linalg.norm(np.asarray(prog[k], np.float64))) for k in names}
    med = statistics.median(rn.values())
    return {k: abs(pn[k] - rn[k]) / max(rn[k], med, 1e-30) for k in names}
