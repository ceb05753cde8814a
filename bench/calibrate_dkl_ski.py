#!/usr/bin/env python3
"""Readings that set the deep-kernel KISS-GP cell's limits, as
``bench/calibrate.py`` gives them for the exact-GP cells: sound runs of the
program, the controls and the faults, each compared with
``bench/reference_dkl_ski.py`` by the harness's comparison and judged
against the configuration's limits.  The benchmark's own runs never run
this.

    python3 bench/calibrate_dkl_ski.py --workload elevators-dkl-ski.train \
        --seeds 1 2 3 ... --control-seeds 1 2 3 \
        --controls bfloat16 high net-bfloat16 mixed --faults half_batch stopped_features

Controls: ``mixed`` is the program's own lower precision (the feature net's
matmuls at the platform's default, one bf16 pass on the TPU); ``high`` and
``bfloat16`` are the reference with every product at that precision, and
``net-bfloat16`` the reference with the network's layers alone at one bf16
pass, each put in the program's place.  Faults: ``half_batch`` is the
reference on half the rows, loss and gradient doubled; ``stopped_features``
is the program with the features' gradient stopped, so that the network
never trains while the GP's hyperparameters do.  ``--conditioning`` adds,
for the first control seed, the start's K^ spectrum and each reference
control's error in K^ (one ``conditioning`` line).

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

from bench.calibrate import emit  # noqa: E402
from bench.common import Run  # noqa: E402
from bench.spec import Spec  # noqa: E402

# the reference's controls: keyword arguments of its trajectory
REFERENCE_CONTROLS = {
    "high": {"precision": "high"},
    "bfloat16": {"precision": "bfloat16"},
    "net-bfloat16": {"net_precision": "bfloat16"},
}
CONTROLS = ("mixed", *REFERENCE_CONTROLS)
FAULTS = ("half_batch", "stopped_features")
POWER_ITERS = 60  # power iterations for a spectral norm; the top eigenvalue
# of K^ stands far from the rest, so its Rayleigh quotient settles in a few


def stopped_features(make_model):
    """``make_model`` with the features' gradient stopped (the fault)."""
    import dataclasses

    import jax

    from repro.gp import FeatureMap

    @dataclasses.dataclass(frozen=True)
    class Stopped(FeatureMap):
        def __call__(self, net, X):
            return jax.lax.stop_gradient(FeatureMap.__call__(self, net, X))

    def make(cfg, precision=None):
        model = make_model(cfg, precision)
        return dataclasses.replace(model, features=Stopped(**dataclasses.asdict(model.features)))

    return make


def conditioning(cfg, X, start, controls) -> dict:
    """At the start: K^'s largest eigenvalue and its noise s2, which is also
    its smallest (W K_UU W' has rank at most m = grid points < n), and per
    reference control the spectral norm of the error in the K^ it builds."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from bench import reference_dkl_ski as reference
    from bench.drivers.train_dkl_ski import features

    @jax.jit
    def spectral_norm(A):
        def it(_, v):
            w = jnp.matmul(A, v, precision=jax.lax.Precision.HIGHEST)
            return w / jnp.linalg.norm(w)

        v = jax.lax.fori_loop(0, POWER_ITERS, it, jnp.ones((A.shape[0],), jnp.float32))
        return jnp.linalg.norm(jnp.matmul(A, v, precision=jax.lax.Precision.HIGHEST))

    params = {k: jnp.asarray(v) for k, v in start.items()}
    khat = jax.jit(partial(reference.khat, bounds=features(cfg)["bounds"],
                           size=cfg["model"]["grid_size"]),
                   static_argnames=("precision", "net_precision"))
    X = jnp.asarray(X)
    with jax.default_matmul_precision("highest"):
        K = khat(params, X)
        out = {"lambda_max": float(spectral_norm(K)),
               "noise": float(reference.softplus(params["raw_noise"]))}
        out["cond"] = out["lambda_max"] / out["noise"]
        for control in controls:
            if control in REFERENCE_CONTROLS:
                err = khat(params, X, **REFERENCE_CONTROLS[control]) - K
                out[f"khat_error.{control}"] = float(spectral_norm(err))
                del err
    return out


def train_readings(r: Run, seeds, control_seeds, controls, faults, conditioned: bool):
    from bench.drivers import train_dkl_ski
    from bench.drivers.train import reference_readings
    from bench.drivers.train_dkl_ski import Prepared, trajectory

    cfg, lr, limits = r.config, r.traffic["lr"], r.config["limits"]["train"]
    for seed in seeds:
        p = Prepared(Run(**{**r.__dict__, "seed": seed}))
        p.free()
        steps, n = len(p.losses), p.X_host.shape[0]
        ref = trajectory(cfg, p.X_host, p.y_host, p.start, lr, steps)
        emit(seed, "sound", p.readings(ref), limits)
        if seed not in control_seeds:
            continue
        residuals = {"reference": ref["residuals"]}
        for control in controls:
            if control == "mixed":
                c = Prepared(Run(**{**r.__dict__, "seed": seed, "precision": "mixed"}))
                c.free()
                readings = c.readings(ref)
            else:
                c = trajectory(cfg, p.X_host, p.y_host, p.start, lr, steps,
                               **REFERENCE_CONTROLS[control])
                residuals[control] = c["residuals"]
                readings = reference_readings(n, c["losses"], c["first_grad"], c["start"],
                                              c["end"], ref)
            emit(seed, f"control.{control}", readings, limits)
        if "half_batch" in faults:
            h = trajectory(cfg, p.X_host[: n // 2], p.y_host[: n // 2], p.start, lr, steps,
                           loss_scale=2.0)
            emit(seed, "half_batch",
                 reference_readings(n, h["losses"], h["first_grad"], h["start"], h["end"], ref),
                 limits)
        if "stopped_features" in faults:
            real = train_dkl_ski.make_model
            train_dkl_ski.make_model = stopped_features(real)
            try:
                f = Prepared(Run(**{**r.__dict__, "seed": seed}))
            finally:
                train_dkl_ski.make_model = real
            f.free()
            emit(seed, "stopped_features", f.readings(ref), limits)
        if conditioned:
            conditioned = False
            record = {"seed": seed, "kind": "conditioning", "residuals": residuals,
                      **conditioning(cfg, p.X_host, p.start, controls)}
            print(json.dumps(record), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--controls", nargs="*", choices=CONTROLS, default=list(CONTROLS))
    ap.add_argument("--faults", nargs="*", choices=FAULTS, default=list(FAULTS))
    ap.add_argument("--conditioning", action="store_true",
                    help="K^'s spectrum and the controls' error in it, first control seed")
    ap.add_argument("--root", default=ROOT)
    args = ap.parse_args(argv)
    spec = Spec(args.root)
    cell = spec.cell(args.workload)
    from bench.run import compile_cache

    compile_cache()
    r = Run(cell=cell, config=spec.config(cell["config"]), traffic=spec.traffic(cell["traffic"]),
            seed=0, seconds=0.0, t0=T0)
    train_readings(r, args.seeds, set(args.control_seeds), args.controls, set(args.faults),
                   args.conditioning)
    return 0


if __name__ == "__main__":
    sys.exit(main())
