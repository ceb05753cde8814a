#!/usr/bin/env python3
"""Readings that set a training cell's limits: sound runs of the program,
the controls and the half-batch fault, each compared with the plain
reference by the harness's own comparison (``reference_readings``) and
judged against the configuration's limits.  The benchmark's own runs never
run this.

    python3 bench/calibrate.py --workload elevators.train --seeds 1 2 3 ... \
        --control-seeds 1 2 3 --controls bfloat16 high mixed

Controls: ``mixed`` is the program's own lower-precision path (bf16 kernel
tiles with an f32 residual refresh); ``high`` and ``bfloat16`` are the
reference computed at that precision and put in the program's place.  The
half-batch fault is the reference on half the rows, loss and gradient
doubled, in the program's place.

Prints one JSON line per reading: {"seed", "kind", "readings", "fails"}.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.common import Run, checks_of  # noqa: E402
from bench.spec import Spec  # noqa: E402

CONTROLS = ("mixed", "high", "bfloat16")


def emit(seed, kind, readings, limits):
    fails = [c.name for c in checks_of(readings, limits, lambda s: None) if not c.ok]
    print(json.dumps({"seed": seed, "kind": kind, "readings": readings, "fails": fails}),
          flush=True)


def train_readings(r: Run, seeds, control_seeds, controls, half: bool):
    from bench import reference
    from bench.drivers.train import Prepared, reference_readings

    lr, limits = r.traffic["lr"], r.config["limits"]["train"]
    for seed in seeds:
        p = Prepared(Run(**{**r.__dict__, "seed": seed}))
        p.free()
        steps, n = len(p.losses), p.X_host.shape[0]
        ref = reference.train_trajectory(p.X_host, p.y_host, lr, steps)
        emit(seed, "sound", p.readings(ref), limits)
        if seed not in control_seeds:
            continue
        for control in controls:
            if control == "mixed":
                c = Prepared(Run(**{**r.__dict__, "seed": seed, "precision": "mixed"}))
                c.free()
                readings = c.readings(ref)
            else:
                c = reference.train_trajectory(p.X_host, p.y_host, lr, steps, precision=control)
                readings = reference_readings(n, c["losses"], c["first_grad"], c["start"],
                                              c["end"], ref)
            emit(seed, f"control.{control}", readings, limits)
        if half:
            h = reference.train_trajectory(p.X_host[: n // 2], p.y_host[: n // 2], lr, steps,
                                           loss_scale=2.0)
            emit(seed, "half_batch",
                 reference_readings(n, h["losses"], h["first_grad"], h["start"], h["end"], ref),
                 limits)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--controls", nargs="*", choices=CONTROLS, default=list(CONTROLS))
    ap.add_argument("--no-half", action="store_true", help="skip the half-batch fault")
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)
    spec = Spec(args.root)
    cell = spec.cell(args.workload)
    from bench.run import compile_cache

    compile_cache()
    r = Run(cell=cell, config=spec.config(cell["config"]), traffic=spec.traffic(cell["traffic"]),
            seed=0, seconds=0.0, t0=T0)
    train_readings(r, args.seeds, set(args.control_seeds), args.controls, not args.no_half)
    return 0


if __name__ == "__main__":
    sys.exit(main())
