"""The BBMM precision policy.

BBMM's entire cost is the repeated kernel matmul inside mBCG, so the one
precision decision that matters is the dtype of the *kernel tiles* and the
tile×RHS products on the MXU.  Everything else — CG vector updates, inner
products, the σ² diagonal, preconditioner solves, gradients — always stays
in float32.

Two policies, named from the user-facing end down to the kernel:

  * ``precision="highest"`` → ``compute_dtype="float32"``: f32 accuracy in
    every stage.  The TPU's MXU multiplies bf16 only, so f32 accuracy there
    is a sum of bf16 products: each f32 operand is split exactly into three
    bf16 pieces, hi + mid + lo.  The kernel-matmul launch
    (``repro.kernels.kernel_matmul``) carries those pieces side by side in
    the lanes that padding to 128 would waste and runs each MXU stage as
    native bf16 passes with f32 accumulation:

      - distances ⟨x, x'⟩: hi·hi + hi·mid + mid·hi + hi·lo + lo·hi +
        mid·mid — the term set of ``Precision.HIGHEST``'s six passes — in
        ONE pass over round_up(6d, 128) lanes (d ≤ 21 fits one lane tile;
        HIGHEST makes six over round_up(d, 128));
      - the tile×RHS product: all nine cross terms of K's and M's splits,
        in three passes over round_up(3t, 128) lanes; used while that beats
        HIGHEST's six over round_up(t, 128), i.e. t ≤ 42, else HIGHEST.

    Dropping mid·lo, lo·mid and lo·lo costs at most ~2⁻²³ of |x||x'| per
    feature, the size of f32's own rounding: this is f32 arithmetic done
    on bf16 hardware, not the mixed policy.  The fused CG step keeps
    ``Precision.HIGHEST`` on f32 operands.
  * ``precision="mixed"``   → ``compute_dtype="bfloat16"``: kernel tiles and
    the tile×RHS product run on ONE bf16 piece each, with f32 accumulation
    (``preferred_element_type=float32``) — one pass per stage and half the
    HBM/all-gather payload for X and M, at bf16's 2⁻⁸ operand rounding.
    CG tolerance semantics are preserved by a periodic f32 residual refresh
    inside mBCG (see ``repro.core.mbcg``).

On TPU, XLA's default f32 matmul is a single bf16 pass, which would
quietly break the "everything else stays f32" half of both policies.  The
engine entry points therefore run under :func:`f32_matmuls` (full f32
contraction for f32 operands; the explicit bf16 stages cast their operands
and are unaffected), and the Pallas kernel names its MXU precision itself.
On CPU both are no-ops.

``compute_dtype`` is the low-level knob threaded through the Pallas kernel,
``prescale_inputs``, the ``KernelOperator`` family and
``LinearOperator.with_compute_dtype``; ``precision`` is the end-to-end knob
on ``BBMMSettings`` / ``ExactGP`` / ``SGPR`` / ``SKI``.  Both accept either
vocabulary — ``normalize_compute_dtype`` maps between them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

PRECISIONS = ("highest", "mixed")

# precision alias → canonical compute_dtype name
_PRECISION_TO_COMPUTE = {"highest": "float32", "mixed": "bfloat16"}

_COMPUTE_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def normalize_compute_dtype(compute_dtype) -> str:
    """Canonical compute-dtype name ('float32' | 'bfloat16').

    Accepts either vocabulary ('highest'/'mixed' or 'float32'/'bfloat16')
    plus actual jnp dtypes, so call sites can pass whichever knob they hold.
    """
    if compute_dtype in (jnp.float32, jnp.bfloat16):
        return jnp.dtype(compute_dtype).name
    name = _PRECISION_TO_COMPUTE.get(compute_dtype, compute_dtype)
    if name not in _COMPUTE_DTYPES:
        raise ValueError(
            f"unknown compute_dtype {compute_dtype!r}; expected one of "
            f"{sorted(_COMPUTE_DTYPES)} or precision {PRECISIONS}"
        )
    return name


def as_jnp_dtype(compute_dtype):
    """The jnp dtype for a compute_dtype/precision name."""
    return _COMPUTE_DTYPES[normalize_compute_dtype(compute_dtype)]


def validate_precision(precision: str) -> str:
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, got {precision!r}")
    return precision


def precision_compute_dtype(precision: str) -> str:
    """End-to-end precision knob → compute_dtype name."""
    return _PRECISION_TO_COMPUTE[validate_precision(precision)]


def is_reduced(compute_dtype) -> bool:
    """True when the policy selects bf16 MXU operands.  Operators must test
    their ``compute_dtype`` field through this (never ``== "bfloat16"``) so
    the 'mixed' alias means the same thing on every construction path."""
    return normalize_compute_dtype(compute_dtype) == "bfloat16"


def f32_matmuls(fn):
    """Decorator: trace/run ``fn`` with f32 matmuls at full f32 precision
    (``jax.default_matmul_precision("highest")``) — see the module
    docstring for why the engine needs it on TPU."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)

    return wrapped
