"""The ONE fit driver behind every GP model (protocol layer of ISSUE 3).

Before this module each of the five models hand-rolled the same Adam loop
(init → jit'd value_and_grad step → float history); now they all delegate
to :func:`fit_gp`, which drives any :class:`repro.gp.model.GPModel`
through the shared path:

    data   = model.prepare_inputs(X)      # hyperparameter-free geometry, once
    params = model.init_params(X)
    loop:    loss, g = value_and_grad(model.loss)(params, data, y, key_i)

Settings/precision plumbing rides on the model itself — ``model.loss``
reads ``model.settings`` (where the ``precision=`` knob was folded by the
model's ``__post_init__``), so the driver is precision-agnostic by
construction.

``grad_mask`` covers the one structured-training variant in the zoo
(SGPR's ``learn_inducing=False`` freezes the inducing locations) without
forking the loop.

Robustness (the training leg of the solve-health layer):

  * non-finite ``X``/``y`` are rejected up front with an actionable error —
    one NaN row would otherwise poison every step silently;
  * the model trains in the mode it was given: ``mode="pallas"`` and
    ``mode="pallas_partitioned"`` launch the Pallas kernel in the forward
    pass and differentiate a checkpointed XLA panel stream in their custom
    VJPs (``pallas_call`` itself has no usable JVP rule), so a fault in
    either raises — the fit never switches to a dense K behind the
    caller's back;
  * every step's loss is checked for finiteness on the host, under the
    model's ``settings.on_failure`` policy: ``raise`` fails the fit,
    ``degrade`` retries the SAME step from the pre-step parameters at
    ``precision="highest"`` (once; the poisoned update is discarded), and
    ``warn`` records the non-finite loss and skips the poisoned update so
    the parameters never absorb NaN gradients.

Each step runs under two host spans, ``fit:dispatch`` (the jitted step's
call) and ``fit:sync`` (reading the loss back), which land in any
``jax.profiler`` capture of a fit beside the device ops they wait for.
"""

from __future__ import annotations

import dataclasses
import math
import time
import warnings
from typing import Callable

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.health import SolveFailure, SolveHealthWarning
from repro.optim import adam


def _require_finite(name: str, arr) -> None:
    bad = int(jax.device_get(jnp.sum(~jnp.isfinite(arr))))
    if bad:
        raise ValueError(
            f"fit_gp: {name} contains {bad} non-finite value(s) (NaN/Inf) "
            f"out of {arr.size}; drop or impute the offending rows before "
            "fitting — a single non-finite entry poisons every MLL solve "
            "and gradient"
        )


def fit_gp(
    model,
    X,
    y,
    *,
    steps: int = 100,
    lr: float = 0.1,
    key=None,
    verbose: bool = False,
    log_every: int = 10,
    grad_mask: Callable | None = None,
):
    """Fit any GPModel with Adam on the mBCG marginal log likelihood.

    Args:
      model: a :class:`repro.gp.model.GPModel` (structural — anything with
        ``prepare_inputs`` / ``init_params`` / ``loss``).
      X, y: training inputs (n, d) and targets (n,).  Must be finite.
      steps, lr: Adam schedule.
      key: PRNG key driving the per-step probe draws (fixed default →
        deterministic histories; models pass their historical defaults).
      verbose / log_every: print ``-mll/n`` every ``log_every`` steps.
      grad_mask: optional pytree→pytree transform applied to each gradient
        before the optimizer update (e.g. zero the inducing-point leaf).

    Returns:
      (params, history) — final parameters and the per-step loss floats.
    """
    key = jax.random.PRNGKey(0) if key is None else key
    X = jnp.asarray(X)
    y = jnp.asarray(y)
    _require_finite("X", X)
    _require_finite("y", y)
    data = model.prepare_inputs(X)
    params = model.init_params(X)
    init, update = adam(lr)
    opt = init(params)

    def make_step(m, d):
        @jax.jit
        def step(params, opt, k):
            loss, g = jax.value_and_grad(m.loss)(params, d, y, k)
            if grad_mask is not None:
                g = grad_mask(g)
            params, opt = update(g, opt, params)
            return params, opt, loss

        return step

    step = make_step(model, data)
    policy = getattr(getattr(model, "settings", None), "on_failure", "warn")

    n = y.shape[-1]
    history = []
    precision_degraded = False
    i = 0
    while i < steps:
        key, sub = jax.random.split(key)
        t_step = time.perf_counter()
        with obs.span("fit:dispatch"):
            params_new, opt_new, loss = step(params, opt, sub)
        with obs.span("fit:sync"):
            loss_f = float(loss)  # host sync — the step is done here
        if obs.active() is not None:
            # per-step training telemetry for gp_top during long fits
            mname = type(model).__name__
            obs.inc("fit_steps_total", model=mname)
            obs.observe(
                "fit_step_seconds", time.perf_counter() - t_step, model=mname
            )
            if math.isfinite(loss_f):
                obs.set_gauge("fit_loss", loss_f, model=mname)
            else:
                obs.inc("fit_nonfinite_steps_total", model=mname)
        if not math.isfinite(loss_f):
            if policy == "raise":
                raise SolveFailure(
                    f"fit_gp: non-finite loss ({loss_f}) at step {i} with "
                    "on_failure='raise'"
                )
            if (
                policy == "degrade"
                and not precision_degraded
                and getattr(model, "settings", None) is not None
                and model.settings.precision != "highest"
            ):
                warnings.warn(
                    f"fit_gp: non-finite loss at step {i}; retrying from the "
                    "pre-step parameters at precision='highest' (the "
                    "poisoned update was discarded)",
                    SolveHealthWarning,
                    stacklevel=2,
                )
                precision_degraded = True
                if getattr(model, "precision", None) is not None:
                    # the model-level knob wins over settings in __post_init__
                    model = dataclasses.replace(model, precision="highest")
                else:
                    model = dataclasses.replace(
                        model,
                        settings=dataclasses.replace(
                            model.settings, precision="highest"
                        ),
                    )
                step = make_step(model, data)
                continue  # retry the SAME step; params/opt were not advanced
            warnings.warn(
                f"fit_gp: non-finite loss at step {i}; skipping the "
                "poisoned update (parameters unchanged this step)",
                SolveHealthWarning,
                stacklevel=2,
            )
            history.append(loss_f)  # honest history: the step DID go bad
            i += 1
            continue
        params, opt = params_new, opt_new
        history.append(loss_f)
        if verbose and i % log_every == 0:
            print(f"step {i:4d}  -mll/n {loss_f/n:.4f}")
        i += 1
    return params, history
