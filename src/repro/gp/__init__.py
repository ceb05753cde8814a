"""GP model zoo on top of the BBMM engine (paper §5).

All models implement the :class:`repro.gp.model.GPModel` structural
protocol and train through the shared :func:`repro.gp.training.fit_gp`
driver; the streaming-capable ones additionally implement
``update_cache`` (see :class:`repro.gp.model.SupportsStreaming`), the
seam :class:`repro.serving.PosteriorSession` serves them through.
"""

from .kernels import (
    RBFKernel,
    MaternKernel,
    DeepKernel,
    KernelOperator,
    CrossKernelOperator,
    sq_dist,
)
from .model import (
    GPModel,
    SupportsStreaming,
    PROTOCOL_METHODS,
    STREAMING_METHODS,
    missing_protocol_methods,
    supports_streaming,
    KrylovCachePredictor,
    WoodburyCache,
    WoodburyCachePredictor,
    build_woodbury_cache,
    woodbury_predict,
    woodbury_update,
)
from .training import fit_gp
from .exact import ExactGP
from .sgpr import SGPR
from .ski import SKI, Grid
from .blr import BayesianLinearRegression
from .dkl import DKLExactGP, FeatureMap, mlp_init, mlp_apply, scale_to_bounds
from .multitask import (
    MultitaskGP,
    MultitaskData,
    to_long_format,
    split_long_format,
)
