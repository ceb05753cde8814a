"""Closed-loop MLL training of deep-kernel KISS-GP: one caller runs Adam
steps back to back, as ``train.py`` does for the exact GP.

The model is ``SKI(features=FeatureMap(...))`` built from the
configuration: the feature net, ScaleToBounds, W rebuilt from the features
inside each step on a static grid, the Kronecker-Toeplitz K_UU.  Set-up
compiles ``fit_gp``'s step (``train.make_step``: ``value_and_grad(model.loss)``
then ``repro.optim.adam``) with data and targets as arguments, drives it
through the first ``check_steps`` steps and hands the same step and state to
the window; each step reads its loss back to the host.  The key is split
inside the compiled step, into the same keys as ``train.py``'s split on the
host, so a step is one dispatch.

Inputs are ``bench/data.py``'s regression at the configuration's (n, d),
each column mapped onto [-1, 1] as GPyTorch's DKL example maps its data.
The starting weights are drawn from the seed by the plain reference's
``init_params`` and given to the program and the reference alike, so both
start from the same numbers and neither takes anything the other made.

``correct`` compares the checked steps against ``bench/reference_dkl_ski.py``
by ``train.reference_readings``: each step's loss gap in nats per row, the
first gradient and the parameters' change, by worst leaf and per leaf,
leaves named flat (``net.0.w``, ..., ``raw_noise``).
"""

from __future__ import annotations

import contextlib
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, reference_dkl_ski as reference, trace
from bench.common import Outcome, Run, checks_of, memory_peak_bytes, now, seed_key
from bench.drivers.train import ADAM_B1, make_step, reference_readings


def inputs(cfg: dict, seed: int):
    """(X, y): the seeded regression, X's columns mapped onto [-1, 1]."""
    X, y = data.regression(cfg["n"], cfg["d"], seed)
    lo, hi = X.min(0), X.max(0)
    return (2.0 * (X - lo) / (hi - lo) - 1.0).astype(np.float32), y


def features(cfg: dict) -> dict:
    f = cfg["model"]["features"]
    return {"widths": tuple(f["widths"]), "bounds": tuple(float(b) for b in f["bounds"])}


def make_model(cfg: dict, precision: str | None = None):
    from repro.core import BBMMSettings
    from repro.gp import SKI, FeatureMap

    return SKI(grid_size=cfg["model"]["grid_size"], features=FeatureMap(**features(cfg)),
               settings=BBMMSettings(**cfg["settings"]), precision=precision)


def nested(flat: dict) -> dict:
    """The program's parameter tree from flat names."""
    layers = reference.layers(flat)
    out = {k: jnp.asarray(v) for k, v in flat.items() if not k.startswith("net.")}
    out["net"] = [{"w": jnp.asarray(flat[f"net.{i}.w"]), "b": jnp.asarray(flat[f"net.{i}.b"])}
                  for i in range(layers)]
    return out


def flat(tree: dict) -> dict:
    """Flat names (``net.0.w``) of the program's parameter tree, as host arrays."""
    out = {k: np.asarray(v) for k, v in tree.items() if k != "net"}
    for i, layer in enumerate(tree["net"]):
        out.update({f"net.{i}.{k}": np.asarray(v) for k, v in layer.items()})
    return out


def keyed(step):
    """``step`` with the key split inside it: (params, opt, data, y, key) ->
    (params, opt, loss, next key)."""

    def run(params, opt, data, y, key):
        key, sub = jax.random.split(key)
        params, opt, loss = step(params, opt, data, y, sub)
        return params, opt, loss, key

    return jax.jit(run)


class Prepared:
    """The compiled step with its state after the checked steps, and what
    those steps produced (flat leaves)."""

    def __init__(self, r: Run):
        cfg = r.config
        t_in = now()
        self.X_host, self.y_host = inputs(cfg, r.seed)
        self.start = reference.init_params(cfg["d"], cfg["model"]["features"]["widths"], r.seed)
        model = make_model(cfg, r.precision)
        X, y = jnp.asarray(self.X_host), jnp.asarray(self.y_host)
        feed = model.prepare_inputs(X)
        params = nested(self.start)
        init, step = make_step(model, r.traffic["lr"])
        opt = init(params)
        key = seed_key(r.seed)
        t_compile = now()
        compiled = keyed(step).lower(params, opt, feed, y, key).compile()
        t_checked = now()

        def one(params, opt, key):
            with trace.span("dispatch", r.tracing):
                params, opt, loss, key = compiled(params, opt, feed, y, key)
            with trace.span("sync", r.tracing):
                loss = float(loss)
            return params, opt, loss, key

        self.one = one
        self.losses = []
        for i in range(cfg["check_steps"]):
            params, opt, loss, key = one(params, opt, key)
            self.losses.append(loss)
            if i == 0:
                self.first_grad = {k: v / (1.0 - ADAM_B1) for k, v in flat(opt.mu).items()}
        self.checked = flat(params)
        self.state = (params, opt, key)
        r.log(f"[setup] before_data_s={t_in - r.t0} data_model_s={t_compile - t_in} "
              f"compile_s={t_checked - t_compile} checked_steps_s={now() - t_checked}")

    def readings(self, ref) -> dict:
        return reference_readings(self.X_host.shape[0], self.losses, self.first_grad,
                                  self.start, self.checked, ref)

    def free(self):
        self.one = self.state = None
        gc.collect()


def trajectory(cfg: dict, X, y, start: dict, lr: float, steps: int, **kw) -> dict:
    """The reference's own steps on (X, y) from ``start``."""
    return reference.train_trajectory(X, y, start, lr, steps, bounds=features(cfg)["bounds"],
                                      size=cfg["model"]["grid_size"], **kw)


def run(r: Run) -> Outcome:
    cfg = r.config
    p = Prepared(r)
    setup_s = now() - r.t0

    params, opt, key = p.state
    steps = failed = 0
    with (trace.capture(r.trace_dir) if r.tracing else contextlib.nullcontext()):
        with trace.span("window", r.tracing):
            t_start = now()
            deadline = t_start + r.seconds
            while True:
                params, opt, loss, key = p.one(params, opt, key)
                steps += 1
                failed += not math.isfinite(loss)
                if now() >= deadline:
                    break
            t_end = now()
    mem = memory_peak_bytes(jax.devices())
    r.log(f"[train] steps={steps} window_s={t_end - t_start} last_loss={loss} "
          f"check_losses={p.losses}")
    del params, opt
    p.free()

    ref = trajectory(cfg, p.X_host, p.y_host, p.start, r.traffic["lr"], len(p.losses))
    r.log(f"[reference] losses={ref['losses']} residuals={ref['residuals']} first_grad_leaf_norms="
          f"{ {k: float(np.linalg.norm(v)) for k, v in ref['first_grad'].items()} }")
    readings = p.readings(ref)
    checks = checks_of(readings, cfg["limits"]["train"], r.log)
    dims = cfg["model"]["features"]["widths"][-1]
    return Outcome(
        setup_s=setup_s,
        e2e={"fit_step_s": (t_end - t_start) / steps},
        attempted=steps, failed=failed, checks=checks, readings=readings,
        layer={"steps": steps, "window_s": t_end - t_start, "n": cfg["n"], "d": cfg["d"],
               "t": cfg["settings"]["num_probes"] + 1,
               "m": cfg["model"]["grid_size"] ** dims, "q": 4 ** dims,
               "widths": [cfg["d"], *cfg["model"]["features"]["widths"]]},
        memory_peak_bytes=mem,
    )
