"""Deep kernel learning (Wilson et al. 2016; the paper's SKI+DKL experiments).

Two ways to put a GP on a learned feature map:

  * ``SKI(features=FeatureMap(...))`` (``gp/ski.py``) — GPyTorch's DKL
    example: a ReLU MLP (``FeatureMap``, published widths 18-1000-500-50-2),
    ``scale_to_bounds`` onto the grid's box, then KISS-GP interpolation on a
    static grid, W rebuilt from the features inside every traced loss so the
    MLL gradient reaches the network.
  * ``DKLExactGP`` — an RBF/Matérn GP on the features through a dense
    kernel operator; the feature map can be a small MLP (built here) or
    *any* backbone from the repro.models zoo (wrap its pooled hidden state —
    see examples/deep_kernel_lm.py).  Gradients flow into network weights
    through BBMM's custom VJP: the network is just another kernel
    hyperparameter.

Because the feature map lives *inside* the kernel, DKL reduces to the
exact-GP serving story on featurized inputs: the full
:class:`repro.gp.model.KrylovCachePredictor` surface (posterior cache,
CG-free cached queries, streaming updates) and the ``precision=`` knob
come for free through the shared protocol layer.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import AddedDiagOperator, BBMMSettings, marginal_log_likelihood
from .exact import KERNELS, _softplus, _inv_softplus, _input_dim
from .kernels import DeepKernel, KernelOperator
from .model import KrylovCachePredictor
from .training import fit_gp


def mlp_init(key, sizes):
    params = []
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        key, sub = jax.random.split(key)
        w = jax.random.normal(sub, (a, b)) * jnp.sqrt(2.0 / a)
        params.append({"w": w, "b": jnp.zeros((b,))})
    return params


def mlp_apply(params, X, act=jax.nn.tanh):
    h = X
    for i, layer in enumerate(params):
        h = h @ layer["w"] + layer["b"]
        if i < len(params) - 1:
            h = act(h)
    return h


def scale_to_bounds(z, lo, hi, bounds):
    """GPyTorch's ``ScaleToBounds``: one global affine map taking [lo, hi]
    (the training features' min and max, gradients flowing through both)
    onto 0.95 of ``bounds``, so every point keeps a cubic stencil inside
    the grid."""
    lower, upper = bounds
    return (z - lo) * (0.95 * (upper - lower) / (hi - lo)) + 0.95 * lower


@dataclasses.dataclass(frozen=True)
class FeatureMap:
    """The DKL feature extractor SKI interpolates from: a ReLU MLP of
    ``widths`` (the last is the grid's dimension), its output scaled onto
    ``bounds``."""

    widths: tuple = (1000, 500, 50, 2)
    bounds: tuple = (-1.0, 1.0)

    @property
    def dims(self) -> int:
        return self.widths[-1]

    def init(self, key, d: int):
        return mlp_init(key, (d, *self.widths))

    def __call__(self, net, X):
        return mlp_apply(net, X, jax.nn.relu)


@dataclasses.dataclass
class DKLExactGP(KrylovCachePredictor):
    hidden: tuple = (32, 32, 2)  # paper maps into a low-dim space for SKI
    kernel_type: str = "rbf"
    feature_fn: callable = None  # override to plug an LM backbone
    settings: BBMMSettings = dataclasses.field(default_factory=BBMMSettings)
    # "highest" | "mixed": same semantics as ExactGP — the kernel-tile ×
    # RHS contractions on the featurized inputs run at bf16 with f32
    # accumulation plus the mBCG f32 residual refresh (the feature-map
    # forward pass itself stays f32).  None follows settings.precision; an
    # explicit value overrides it unconditionally.
    precision: str | None = None
    # fused-CG knob: the deep kernel is non-stationary, so the Pallas fused
    # step does not apply to DKL's operator — True falls back to the
    # unfused loop.  None follows ``settings.fuse_cg``.
    fuse_cg: bool | None = None

    def __post_init__(self):
        if self.precision is not None:
            self.settings = dataclasses.replace(
                self.settings, precision=self.precision
            )
        if self.fuse_cg is not None:
            self.settings = dataclasses.replace(self.settings, fuse_cg=self.fuse_cg)

    # -- GPModel protocol: inputs / parameterization --------------------------
    def prepare_inputs(self, X):
        return X

    def init_params(self, X, key=None):
        d = _input_dim(X)
        key = jax.random.PRNGKey(7) if key is None else key
        return {
            "net": mlp_init(key, (d,) + self.hidden) if self.feature_fn is None else {},
            "raw_lengthscale": jnp.zeros(()) + _inv_softplus(jnp.float32(0.5)),
            "raw_outputscale": _inv_softplus(jnp.float32(1.0)),
            "raw_noise": _inv_softplus(jnp.float32(0.1)),
        }

    def _features(self):
        return self.feature_fn if self.feature_fn is not None else mlp_apply

    def kernel(self, params):
        base = KERNELS[self.kernel_type](
            lengthscale=_softplus(params["raw_lengthscale"]),
            outputscale=_softplus(params["raw_outputscale"]),
        )
        return DeepKernel(base=base, net_params=params["net"], feature_fn=self._features())

    def operator(self, params, data):
        return AddedDiagOperator(
            KernelOperator(kernel=self.kernel(params), X=data, mode="dense"),
            _softplus(params["raw_noise"]),
        )

    def noise(self, params):
        return _softplus(params["raw_noise"])

    def loss(self, params, data, y, key):
        return -marginal_log_likelihood(self.operator(params, data), y, key, self.settings)

    def fit(self, X, y, *, steps=150, lr=0.01, key=None, verbose=False):
        key = jax.random.PRNGKey(8) if key is None else key
        return fit_gp(
            self, X, y, steps=steps, lr=lr, key=key, verbose=verbose, log_every=20
        )

    # posterior_cache / predict_cached / predict / update_cache:
    # inherited from KrylovCachePredictor — the exact-GP cache on
    # featurized inputs (the deep kernel featurizes internally)
