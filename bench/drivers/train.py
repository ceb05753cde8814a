"""Closed-loop MLL training: one caller runs Adam steps back to back.

Set-up builds ONE compiled step with its state (``fit_gp``'s step:
``value_and_grad(model.loss)`` then ``repro.optim.adam``'s update), drives
it from the seed through the first ``check_steps`` steps, and hands the
same step, parameters and optimizer state to the window.  Each step, in
set-up and window alike, splits the key, calls the step and reads the loss
back to the host, as ``fit_gp`` does.  The data and targets are arguments
of the step, not constants of it, so a run with a new seed finds the
compiled step in the persistent cache.

``correct`` compares what those first steps produced against the plain
reference's own steps from the same start: each step's loss, the first
gradient (from the optimizer's first moment after one step) and the
parameters' change after the last checked step.
"""

from __future__ import annotations

import contextlib
import gc
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import data, reference, trace
from bench.common import Outcome, Run, checks_of, leaf_gaps, memory_peak_bytes, now, seed_key

ADAM_B1 = 0.9  # repro.optim.adam's default first-moment decay
MEDIAN_LEAF_SHARE = 1e-3  # leaves whose reference gradient is below this
# share of the median leaf's move by round-off alone under Adam


def make_model(config: dict, precision: str | None = None):
    from repro.core import BBMMSettings
    from repro.gp import ExactGP

    return ExactGP(**config["model"], settings=BBMMSettings(**config["settings"]),
                   precision=precision)


def make_step(model, lr: float):
    """``fit_gp``'s step with data and targets as arguments."""
    from repro.optim import adam

    init, update = adam(lr)

    def step(params, opt, data, y, key):
        loss, g = jax.value_and_grad(model.loss)(params, data, y, key)
        params, opt = update(g, opt, params)
        return params, opt, loss

    return init, jax.jit(step)


class Prepared:
    """The compiled step with its state after the checked steps, and what
    those steps produced."""

    def __init__(self, r: Run):
        cfg = r.config
        n, d = cfg["n"], cfg["d"]
        t_in = now()
        self.X_host, self.y_host = data.regression(n, d, r.seed)
        model = make_model(cfg, r.precision)
        X, y = jnp.asarray(self.X_host), jnp.asarray(self.y_host)
        feed = model.prepare_inputs(X)
        params = model.init_params(X)
        init, step = make_step(model, r.traffic["lr"])
        opt = init(params)
        key = seed_key(r.seed)
        t_compile = now()
        compiled = step.lower(params, opt, feed, y, key).compile()
        t_checked = now()

        def one(params, opt, key):
            with trace.span("dispatch", r.tracing):
                key, sub = jax.random.split(key)
                params, opt, loss = compiled(params, opt, feed, y, sub)
            with trace.span("sync", r.tracing):
                loss = float(loss)
            return params, opt, loss, key

        self.one = one
        self.start = jax.tree.map(np.asarray, params)
        self.losses = []
        for i in range(cfg["check_steps"]):
            params, opt, loss, key = one(params, opt, key)
            self.losses.append(loss)
            if i == 0:
                self.first_grad = jax.tree.map(
                    lambda m: np.asarray(m) / (1.0 - ADAM_B1), opt.mu)
        self.checked = jax.tree.map(np.asarray, params)
        self.state = (params, opt, key)
        r.log(f"[setup] before_data_s={t_in - r.t0} data_model_s={t_compile - t_in} "
              f"compile_s={t_checked - t_compile} checked_steps_s={now() - t_checked}")

    def readings(self, ref) -> dict:
        return reference_readings(self.X_host.shape[0], self.losses, self.first_grad,
                                  self.start, self.checked, ref)

    def free(self):
        self.one = self.state = None
        gc.collect()


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

    ref = reference.train_trajectory(p.X_host, p.y_host, r.traffic["lr"], len(p.losses))
    r.log(f"[reference] losses={ref['losses']} residuals={ref['residuals']} first_grad_leaf_norms="
          f"{ {k: float(np.linalg.norm(v)) for k, v in ref['first_grad'].items()} }")
    readings = p.readings(ref)
    checks = checks_of(readings, cfg["limits"]["train"], r.log)
    n, d = cfg["n"], cfg["d"]
    t = cfg["settings"]["num_probes"] + 1
    return Outcome(
        setup_s=setup_s,
        e2e={"fit_step_s": (t_end - t_start) / steps},
        attempted=steps, failed=failed, checks=checks, readings=readings,
        layer={"steps": steps, "window_s": t_end - t_start, "n": n, "d": d, "t": t},
        memory_peak_bytes=mem,
    )


def reference_readings(n, losses, first_grad, start, end, ref) -> dict:
    """The compared numbers of one run against one reference trajectory:
    each checked step's loss gap in nats per row, and the first gradient
    and the parameters' change after the checked steps, each by the worst
    leaf and by each leaf.  ``change.main`` is the change of the leaf with
    the largest reference gradient: Adam moves a leaf whose gradient nears
    zero within the checked steps by a step that probe noise can flip, so
    the worst leaf's change swings from seed to seed while the main leaf's
    does not.  The configuration's limits say which numbers are compared."""
    out = {}
    for i, (lp, lr_) in enumerate(zip(losses, ref["losses"])):
        out[f"loss.step{i}"] = abs(lp - lr_) / n
    gaps = leaf_gaps(first_grad, ref["first_grad"])
    out["grad"] = max(gaps.values())
    out.update({f"grad.{k}": v for k, v in gaps.items()})
    g_norm = {k: float(np.linalg.norm(np.asarray(v))) for k, v in ref["first_grad"].items()}
    med = float(np.median(list(g_norm.values())))
    moved = [k for k, v in g_norm.items() if v >= MEDIAN_LEAF_SHARE * med]
    prog_change = {k: np.asarray(end[k]) - np.asarray(start[k]) for k in end}
    ref_change = {k: np.asarray(ref["end"][k]) - np.asarray(ref["start"][k]) for k in end}
    gaps = leaf_gaps(prog_change, ref_change, keep=moved)
    out["change"] = max(gaps.values())
    out["change.main"] = gaps[max(g_norm, key=g_norm.get)]
    out.update({f"change.{k}": v for k, v in gaps.items()})
    return out
