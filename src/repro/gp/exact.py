"""Exact GP regression through the BBMM engine (paper §6 "Exact").

Training: the shared Adam driver (``repro.gp.training.fit_gp``) on the raw
(log) hyperparameters of the kernel + noise, gradients from the
custom-VJP marginal log likelihood.  ``batched_loss`` evaluates b
hyperparameter sets (multi-restart training) in ONE fused engine call via
the batched mBCG path.
Prediction/serving: inherited from
:class:`repro.gp.model.KrylovCachePredictor` — ``predict`` builds a
:class:`repro.core.PosteriorCache` (one engine call) and serves the mean
from it; ``predict_cached`` re-serves mean *and* variance from the same
cache with zero CG iterations — O(n·s + n·m) per request, the
serving-traffic path; ``update_cache`` streams data appends in via
warm-started CG with Krylov-basis recycling.
"""

from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import (
    AddedDiagOperator,
    BatchDenseOperator,
    BBMMSettings,
    marginal_log_likelihood,
)
from .kernels import KernelOperator, RBFKernel, MaternKernel
from .model import KrylovCachePredictor
from .training import fit_gp


def _softplus(x):
    return jnp.logaddexp(x, 0.0)


def _inv_softplus(y):
    return jnp.log(jnp.expm1(y))


def _input_dim(X) -> int:
    """Protocol canonical form is the (n, d) input array; a bare int d is
    accepted for convenience at direct call sites."""
    return X if isinstance(X, int) else X.shape[-1]


KERNELS = {"rbf": RBFKernel, "matern52": partial(MaternKernel, nu=2.5),
           "matern32": partial(MaternKernel, nu=1.5), "matern12": partial(MaternKernel, nu=0.5)}


@dataclasses.dataclass
class ExactGP(KrylovCachePredictor):
    kernel_type: str = "rbf"
    # dense | blocked | pallas | pallas_partitioned (the blackbox matmul
    # impl; "pallas_partitioned" streams K one row-panel at a time — panel
    # height / budget come from settings.panel_rows / panel_budget_bytes,
    # backend from ``panel_backend`` — and trains natively: its matmul
    # carries a custom VJP that checkpoints the backward panel stream)
    mode: str = "dense"
    block_size: int = 512
    panel_backend: str = "auto"  # pallas_partitioned: auto | pallas | xla
    settings: BBMMSettings = dataclasses.field(default_factory=BBMMSettings)
    # end-to-end precision knob: "highest" (all f32) or "mixed" (bf16 kernel
    # tiles + f32 accumulation + periodic f32 residual refresh in mBCG).
    # None (default) follows ``settings.precision``; an explicit value wins
    # over it unconditionally — so replace(gp, precision="highest") really
    # does switch a mixed model back.  ``settings.precision`` is what the
    # engine reads either way.
    precision: str | None = None
    # fused-CG knob: True runs each mBCG iteration as ONE fused kernel
    # launch when the operator advertises it (mode="pallas"/"pallas_sharded"
    # — dense/blocked fall back to the unfused loop).  Requires
    # precond_rank=0 (the pivoted-Cholesky solve cannot fuse; mbcg raises).
    # None follows ``settings.fuse_cg``; an explicit value wins.
    fuse_cg: bool | None = None
    ard: bool = False  # one lengthscale per input dim in init_params

    def __post_init__(self):
        if self.precision is not None:
            self.settings = dataclasses.replace(
                self.settings, precision=self.precision
            )
        if self.fuse_cg is not None:
            self.settings = dataclasses.replace(self.settings, fuse_cg=self.fuse_cg)

    # -- GPModel protocol: inputs / parameterization --------------------------
    def prepare_inputs(self, X):
        """Exact GP has no hyperparameter-free geometry: data IS X."""
        return X

    def init_params(self, X, key=None):
        d = _input_dim(X)
        ell0 = jnp.zeros((d,) if self.ard else ()) + _inv_softplus(jnp.float32(0.5))
        return {
            "raw_lengthscale": ell0,
            "raw_outputscale": _inv_softplus(jnp.float32(1.0)),
            "raw_noise": _inv_softplus(jnp.float32(0.1)),
        }

    def kernel(self, params):
        ctor = KERNELS[self.kernel_type]
        return ctor(
            lengthscale=_softplus(params["raw_lengthscale"]),
            outputscale=_softplus(params["raw_outputscale"]),
        )

    def operator(self, params, data) -> AddedDiagOperator:
        extra = {}
        if self.mode == "pallas_partitioned":
            extra = {
                "panel_rows": self.settings.panel_rows,
                "panel_budget_bytes": self.settings.panel_budget_bytes,
                "panel_backend": self.panel_backend,
            }
        base = KernelOperator(
            kernel=self.kernel(params), X=data, mode=self.mode,
            block_size=self.block_size, **extra,
        )
        return AddedDiagOperator(base, _softplus(params["raw_noise"]))

    def noise(self, params):
        return _softplus(params["raw_noise"])

    # -- training -------------------------------------------------------------
    def loss(self, params, data, y, key):
        return -marginal_log_likelihood(self.operator(params, data), y, key, self.settings)

    def batched_operator(self, params_batch, X) -> AddedDiagOperator:
        """K̂ for a stack of b hyperparameter sets as ONE batched operator.

        Every leaf of ``params_batch`` carries a leading (b,) dim (e.g. from
        ``jax.tree.map(jnp.stack, ...)``).  The b kernel matrices are
        materialized batched — the engine then solves all b problems in a
        single fused mBCG program."""
        Ks = jax.vmap(lambda p: self.kernel(p)(X, X))(params_batch)
        return AddedDiagOperator(
            BatchDenseOperator(Ks), _softplus(params_batch["raw_noise"])
        )

    def batched_loss(self, params_batch, X, y, key):
        """(b,) negative MLLs for b hyperparameter sets in one engine call.

        ``y`` may be (n,) (shared targets, broadcast) or (b, n)."""
        op = self.batched_operator(params_batch, X)
        b = op.base.batch
        yb = jnp.broadcast_to(y, (b, y.shape[-1])) if y.ndim == 1 else y
        return -marginal_log_likelihood(op, yb, key, self.settings)

    def fit(self, X, y, *, steps=100, lr=0.1, key=None, verbose=False):
        key = jax.random.PRNGKey(0) if key is None else key
        return fit_gp(self, X, y, steps=steps, lr=lr, key=key, verbose=verbose)

    # posterior_cache / predict_cached / predict / update_cache:
    # inherited from KrylovCachePredictor (repro.gp.model)
