"""Jit'd public wrappers for the fused kernel matmul.

Three layers:

  * :func:`prescale_inputs` — the once-per-solve work: ARD lengthscale
    division.  Hoisted out of the CG loop via ``KernelOperator.prepare()``
    so it is paid once per solve, not once per iteration.  The feature dim
    keeps its true width d: the launch lays it into the MXU's lanes.
  * :func:`fused_kernel_matmul` / :func:`fused_kernel_matmul_prescaled` —
    single-device entry points (edge masking is in-kernel; M is never padded
    along its rows).  Under ``compute_dtype="float32"`` they pack the bf16
    splits of X and M into the lanes (scope ``mxu.split_bf16``; see
    ``kernel_matmul``'s module docstring).
  * :func:`sharded_kernel_matmul` — ``shard_map`` row-partitioned execution:
    each of D devices keeps only its (n/D × bm) kernel tiles in VMEM and the
    only collective per matmul is ONE all-gather of the (n, t) RHS —
    O(n·t) communication against O(n²·(d+t)/D) compute, the multi-device
    extension of BBMM from Wang et al. 2019.
  * :func:`fused_cg_step_prescaled` / :func:`sharded_fused_cg_step_prescaled`
    — the whole mBCG iteration as ONE launch (state updates + K̂·D + the
    per-column reductions; see ``kernel_matmul.fused_cg_step_pallas``).
    These are the :data:`repro.core.mbcg.CGStepFn` implementations the
    ``KernelOperator`` family advertises through ``fused_cg_step_fn``; the
    sharded form all-gathers the (R, V, D) column state (f32 — CG state
    never loses bits in flight) and ``psum``s the (4, t) reductions.
  * :func:`panel_fused_cg_step_prescaled` — the *partitioned* fused CG
    iteration: the same fused kernel launched once per (panel_rows × n)
    row-panel via ``row_offset``, with the partial [dᵀV; rᵀr; rᵀV; vᵀV]
    reductions carried across the panel loop in a loop-carried (4, t) slab.
    Each panel's prologue touches only its own row band (state is updated
    once per iteration, not once per panel) and the column-side (R, V, D)
    arrays are the full *previous-iteration* state, so the on-the-fly
    direction recompute inside the kernel sees consistent columns no
    matter which panel runs first.  ``sharded_fused_cg_step_prescaled``
    takes ``panel_rows=`` to stream each device's contiguous row band
    through this loop, with the carried reductions summed across devices
    once per iteration in deterministic device order.

Every entry point takes a ``compute_dtype`` ('float32' | 'bfloat16', with
the 'highest'/'mixed' precision aliases accepted) that selects the MXU
operand dtype per ``repro.core.precision``: the operand casts below are the
*policy*, not incidental — M and the pre-scaled X are brought to exactly
``compute_dtype`` (downcast for bf16, upcast for f64 — the Pallas kernel is
an f32-accumulate kernel either way), and the sharded path's all-gather
moves the half-width payload when mixed.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.precision import as_jnp_dtype, normalize_compute_dtype
from .kernel_matmul import (
    _FUSED_STATE_SLABS,
    FUSED_CG_STEP,
    PANEL_FUSED_CG_STEP,
    fused_cg_step_pallas,
    kernel_matmul_pallas,
    split_bf16,
)

#: ``jax.named_scope`` around the packed f32 path of one kernel-matrix
#: product (the packing, the launch and the lane-group sum): a device trace
#: tells from it which ``kernel_matmul`` events ran on bf16 splits.
SPLIT_SCOPE = "mxu.split_bf16"

LANES = 128


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _on_tpu():
    return jax.default_backend() == "tpu"


def prescale_inputs(X, lengthscale, compute_dtype="float32"):
    """X/ℓ (ARD broadcasts a (d,) ℓ per-dimension), (n, d).

    This is everything about X the kernel needs that does not change across
    CG iterations — call once per solve.  The result is stored at
    ``compute_dtype``: under the mixed policy X lives in bf16 from here on,
    halving its HBM footprint and (sharded) broadcast payload; the division
    itself always runs in the input precision first.  It keeps its true d,
    which the packed f32 path needs; each launch pads its own operands to
    the 128 lanes."""
    return (X / lengthscale).astype(as_jnp_dtype(compute_dtype))


def lane_aligned(Xs):
    """Pre-scaled X with its features zero-padded to the 128 lanes that the
    single-piece launches (the mixed policy, the fused CG step) contract
    over; a no-op when aligned, so the fused-step factories pad once per
    solve."""
    return _pad_to(Xs, LANES, 1)


def split_product_pays(t):
    """Whether the packed product, three bf16 passes over ⌈3t/128⌉ lane
    tiles, takes fewer passes than HIGHEST's six over ⌈t/128⌉: t ≤ 42."""
    return 3 * -(-3 * t // LANES) < 6 * -(-t // LANES)


class SplitOperands(NamedTuple):
    """X's side of the packed f32 launch (:func:`pack_split_operands`)."""

    rows: jax.Array  # (rows, round_up(6d, 128)) bf16 [hi, hi, mid, hi, lo, mid]
    cols: jax.Array  # (cols, round_up(6d, 128)) bf16 [hi, mid, hi, lo, hi, mid]
    row_norms: jax.Array  # (rows, 1) f32 ‖x‖²
    col_norms: jax.Array  # (1, cols) f32 ‖x‖²


def pack_split_operands(Xs_rows, Xs_cols) -> SplitOperands:
    """The distance stage's packed bf16 operands and f32 squared norms.

    Six d-wide groups per row, [hi, hi, mid, hi, lo, mid] of the rows
    against [hi, mid, hi, lo, hi, mid] of the columns, lane-padded to
    round_up(6d, 128): one bf16 pass returns the six-term sum of
    ``Precision.HIGHEST``.  A pure function of the pre-scaled X: a solve
    packs once (``PreparedPallasKernelOperator``), a bare call per call."""
    with jax.named_scope(SPLIT_SCOPE):
        X1 = Xs_rows.astype(jnp.float32)
        X2 = Xs_cols.astype(jnp.float32)
        h1, m1, l1 = split_bf16(X1)
        h2, m2, l2 = split_bf16(X2)
        return SplitOperands(
            _pad_to(jnp.concatenate([h1, h1, m1, h1, l1, m1], axis=1), LANES, 1),
            _pad_to(jnp.concatenate([h2, m2, h2, l2, h2, m2], axis=1), LANES, 1),
            jnp.sum(X1 * X1, axis=1, keepdims=True),
            jnp.sum(X2 * X2, axis=1)[None, :],
        )


@partial(jax.jit, static_argnames=("kernel_type", "bn", "bm", "interpret"))
def split_kernel_matmul(
    packed: SplitOperands,
    M,
    outputscale,
    sigma2,
    row_offset=0,
    *,
    kernel_type="rbf",
    bn=256,
    bm=512,
    interpret=None,
):
    """(K(X1,X2)+σ²I) @ M under ``compute_dtype="float32"``, from X's packed
    splits: M's own split where :func:`split_product_pays` (else f32 and a
    HIGHEST product), ONE ``kernel_matmul`` launch, the lane groups summed.
    Returns f32 (…, rows, t) like :func:`fused_kernel_matmul_prescaled`."""
    if interpret is None:
        interpret = not _on_tpu()
    with jax.named_scope(SPLIT_SCOPE):
        squeeze = M.ndim == 1
        M = (M[:, None] if squeeze else M).astype(jnp.float32)
        t = M.shape[-1]
        split_rhs = split_product_pays(t)
        if split_rhs:
            M = jnp.concatenate(split_bf16(M, rounded=False), axis=-1)
        if not interpret:
            # compiled (Mosaic) path: keep the tile's trailing dim a multiple
            # of the 128-lane MXU — the row dim needs no padding (masked)
            M = _pad_to(M, LANES, M.ndim - 1)
        out = kernel_matmul_pallas(
            packed.rows, packed.cols, M, jnp.asarray(outputscale),
            jnp.asarray(sigma2), row_offset,
            norms=(packed.row_norms, packed.col_norms),
            kernel_type=kernel_type, bn=bn, bm=bm, interpret=interpret,
        )
        if split_rhs:
            hi, mid, lo = (out[..., k * t : (k + 1) * t] for k in range(3))
            out = (lo + mid) + hi
        else:
            out = out[..., :t]
        return out[..., 0] if squeeze else out


#: Default working-set budget for one streamed row-panel of K (bytes).
#: The partitioned path's peak live tile is one (panel_rows × n) slab —
#: the XLA backend materializes it outright, the Pallas backend bounds it
#: by (bn × bm) VMEM tiles — so this caps panel_rows ≈ budget / (n·4).
PANEL_BUDGET_BYTES = 128 * 1024 * 1024

#: Panel heights are floored to this multiple so pallas row tiles (bn=256)
#: and the 128-lane grid stay aligned; also the minimum viable panel.
PANEL_ALIGN = 128

#: Never stream panels taller than this even when the budget allows —
#: beyond it the panel is no longer "small vs n" and the streaming loop
#: adds launch overhead without memory benefit.
MAX_PANEL_ROWS = 8192


def choose_panel_rows(
    n, *, budget_bytes=None, itemsize=4, rhs_cols=0, batch=1, fused=False
):
    """Largest aligned panel height whose streamed working set fits the
    byte budget — the VMEM/HBM auto-chooser behind ``panel_rows=0``.

    The plain-matmul working set is the (panel_rows × n) kernel slab.  With
    ``fused=True`` the chooser budgets the *fused CG step's* working set
    instead: on top of the kernel slab, each panel launch keeps
    ``_FUSED_STATE_SLABS`` f32 (batch, panel_rows, t) row-state slabs live
    (U/R/D/V in and out), and the whole iteration holds the f32 (R, V, D)
    column state plus the carried (4, t) reduction slab resident — without
    accounting for those, a "within budget" panel height silently blows
    ``panel_budget_bytes`` the moment ``fuse_cg=True`` runs.  ``rhs_cols``
    (t) and ``batch`` size that state; they are trace-time shape constants.

    Returns a multiple of :data:`PANEL_ALIGN` in
    [PANEL_ALIGN, min(n, MAX_PANEL_ROWS)]; at very large n (where even one
    aligned panel row-slab exceeds the budget) it returns PANEL_ALIGN —
    the floor below which the pallas grid cannot shrink."""
    if n <= 0:
        raise ValueError(f"n must be positive, got {n}")
    budget = PANEL_BUDGET_BYTES if budget_bytes is None else int(budget_bytes)
    if budget <= 0:
        raise ValueError(f"budget_bytes must be positive, got {budget}")
    per_row = n * itemsize
    overhead = 0
    if fused:
        t = max(int(rhs_cols), 1)
        b = max(int(batch), 1)
        per_row += _FUSED_STATE_SLABS * b * t * 4
        overhead = 3 * n * b * t * 4 + 4 * t * 4
    rows = max(budget - overhead, 0) // max(per_row, 1)
    rows = (rows // PANEL_ALIGN) * PANEL_ALIGN
    rows = max(PANEL_ALIGN, min(rows, MAX_PANEL_ROWS))
    return min(rows, _ceil_to(n, PANEL_ALIGN))


def _ceil_to(x, mult):
    return -(-x // mult) * mult


@partial(
    jax.jit,
    static_argnames=("kernel_type", "bn", "bm", "interpret", "compute_dtype"),
)
def fused_kernel_matmul_prescaled(
    Xs_rows,
    Xs_cols,
    M,
    outputscale,
    sigma2,
    row_offset=0,
    *,
    kernel_type="rbf",
    bn=256,
    bm=512,
    interpret=None,
    compute_dtype="float32",
):
    """(K(X1,X2)+σ²I) @ M for pre-scaled inputs. Returns f32 (…, rows, t).

    A leading batch dim on M ((b, n, t)) runs as a native batch grid
    dimension of ONE pallas_call — every batch element consumes the X tiles
    already resident in VMEM (b× fewer X-tile loads than the vmapped
    formulation; see ``kernel_matmul.tile_load_counts``).

    M is brought to ``compute_dtype`` per the precision policy — the one
    deliberate dtype decision of this entry point: under ``"float32"`` X and
    M go in as packed bf16 splits of their f32 values (f64 callers get the
    documented f32-accumulate semantics, bf16 callers under the 'highest'
    policy the full-precision MXU path), under ``"bfloat16"`` as one bf16
    piece each."""
    if interpret is None:
        interpret = not _on_tpu()
    compute_dtype = normalize_compute_dtype(compute_dtype)
    kw = dict(kernel_type=kernel_type, bn=bn, bm=bm, interpret=interpret)
    if compute_dtype == "float32":
        return split_kernel_matmul(
            pack_split_operands(Xs_rows, Xs_cols), M, outputscale, sigma2,
            row_offset, **kw,
        )
    squeeze = M.ndim == 1
    if squeeze:
        M = M[:, None]
    t0 = M.shape[-1]
    if not interpret:
        # compiled (Mosaic) path: keep the tile's trailing dim a multiple of
        # the 128-lane MXU — the row dim needs no padding (in-kernel masked)
        M = _pad_to(M, LANES, M.ndim - 1)
    M = M.astype(as_jnp_dtype(compute_dtype))
    out = kernel_matmul_pallas(
        lane_aligned(Xs_rows), lane_aligned(Xs_cols), M, jnp.asarray(outputscale),
        jnp.asarray(sigma2), row_offset, compute_dtype=compute_dtype, **kw,
    )
    out = out[..., :t0]
    return out[..., 0] if squeeze else out


def fused_kernel_matmul(
    X,
    M,
    lengthscale,
    outputscale,
    sigma2,
    *,
    kernel_type="rbf",
    bn=256,
    bm=512,
    interpret=None,
    compute_dtype="float32",
):
    """(K(X,X)+σ²I) @ M via the Pallas kernel (any n — no padding of M)."""
    Xs = prescale_inputs(X, lengthscale, compute_dtype)
    return fused_kernel_matmul_prescaled(
        Xs,
        Xs,
        M,
        outputscale,
        sigma2,
        kernel_type=kernel_type,
        bn=bn,
        bm=bm,
        interpret=interpret,
        compute_dtype=compute_dtype,
    )


def _stationary_kernel_type(kernel):
    from repro.gp.kernels import RBFKernel, MaternKernel

    if isinstance(kernel, RBFKernel):
        return "rbf"
    if isinstance(kernel, MaternKernel):
        return {0.5: "matern12", 1.5: "matern32", 2.5: "matern52"}[kernel.nu]
    raise TypeError(f"pallas path supports stationary kernels, got {kernel}")


def kernel_matmul(kernel, X, M, compute_dtype="float32"):
    """LinearOperator-facing dispatch: map a repro.gp kernel object onto the
    fused Pallas call (no σ² — the AddedDiagOperator adds it outside)."""
    return fused_kernel_matmul(
        X,
        M,
        kernel.lengthscale,
        kernel.outputscale,
        jnp.float32(0.0),
        kernel_type=_stationary_kernel_type(kernel),
        compute_dtype=compute_dtype,
    )


def sharded_kernel_matmul_prescaled(
    Xs,
    M,
    outputscale,
    mesh,
    axes=("data",),
    *,
    kernel_type="rbf",
    bn=256,
    bm=512,
    interpret=None,
    compute_dtype="float32",
):
    """Row-partitioned fused kernel matmul for pre-scaled inputs.

    Layout: Xs replicated (n·d is small), M row-sharded over ``axes``.  Each
    device all-gathers M (the only collective), slices its own row band of
    Xs, and runs the Pallas kernel with the band's global ``row_offset`` so
    tile coordinates — and the σ² diagonal, were it nonzero — stay globally
    correct.  Output is row-sharded like M.

    A leading batch dim on M ((b, n, t), batch replicated, rows sharded)
    flows straight through: the per-device call is the native-batch-grid
    Pallas kernel with this band's ``row_offset`` — batched sharded
    execution with no extra machinery.  Under the mixed policy M is cast to
    bf16 *before* the all-gather, so the one collective moves half the bytes.
    """
    from repro.distributed.sharding import mesh_axis_sizes, row_shard_spec, unchecked_shard_map

    compute_dtype = normalize_compute_dtype(compute_dtype)
    squeeze = M.ndim == 1
    if squeeze:
        M = M[:, None]
    n = Xs.shape[0]
    sizes = mesh_axis_sizes(mesh)
    shards = 1
    for a in axes:
        shards *= sizes[a]
    if n % shards != 0:
        raise ValueError(f"n={n} must divide evenly over {shards} shards")
    row_axis = M.ndim - 2

    def body(Xs_full, M_loc, outputscale):
        M_full = jax.lax.all_gather(M_loc, axes, axis=row_axis, tiled=True)
        idx = jax.lax.axis_index(axes)
        n_loc = n // shards
        X_loc = jax.lax.dynamic_slice_in_dim(Xs_full, idx * n_loc, n_loc, axis=0)
        return fused_kernel_matmul_prescaled(
            X_loc,
            Xs_full,
            M_full,
            outputscale,
            jnp.float32(0.0),
            row_offset=idx * n_loc,
            kernel_type=kernel_type,
            bn=bn,
            bm=bm,
            interpret=interpret,
            compute_dtype=compute_dtype,
        )

    out = unchecked_shard_map(
        body,
        mesh,
        in_specs=(P(None, None), row_shard_spec(M.ndim, axes), P()),
        out_specs=row_shard_spec(M.ndim, axes),
    )(
        Xs,
        M.astype(as_jnp_dtype(compute_dtype)),
        jnp.asarray(outputscale, jnp.float32),
    )
    return out[..., 0] if squeeze else out


def sharded_kernel_matmul(
    kernel,
    X,
    M,
    mesh,
    axes=("data",),
    *,
    bn=256,
    bm=512,
    interpret=None,
    compute_dtype="float32",
):
    """Row-partitioned fused kernel matmul K(X,X) @ M over a device mesh
    (convenience wrapper: prescales per call — the CG hot path goes through
    ``KernelOperator.prepare()`` so prescaling is paid once per solve)."""
    return sharded_kernel_matmul_prescaled(
        prescale_inputs(X, kernel.lengthscale, compute_dtype),
        M,
        kernel.outputscale,
        mesh,
        axes,
        kernel_type=_stationary_kernel_type(kernel),
        bn=bn,
        bm=bm,
        interpret=interpret,
        compute_dtype=compute_dtype,
    )


# ---------------------------------------------------------------------------
# Fused CG step (one pallas_call per mBCG iteration)
# ---------------------------------------------------------------------------


def _flatten_state(arr, n, t):
    """(..., n, t) → (b, n, t) with the leading dims flattened (b=1 if none)."""
    lead = arr.shape[:-2]
    return arr.reshape((-1, n, t)) if lead else arr.reshape((1, n, t)), lead


@partial(
    jax.jit,
    static_argnames=("kernel_type", "bn", "bm", "interpret", "compute_dtype", "name"),
)
def _fused_cg_step_padded(
    Xs_rows,
    Xs_cols,
    U,
    R,
    D,
    V,
    R_cols,
    D_cols,
    V_cols,
    alpha,
    beta,
    gamma,
    outputscale,
    sigma2,
    row_offset=0,
    *,
    kernel_type="rbf",
    bn=256,
    bm=512,
    interpret=None,
    compute_dtype="float32",
    name=FUSED_CG_STEP,
):
    """Shared core of the fused CG step wrappers: flatten leading batch dims,
    lane-pad the features and (compiled mode) the probe dim, run the fused
    kernel (its
    ``pallas_call`` named ``name``), restore shapes.  Padded probe columns are all-zero state with α=β=γ=0, so they
    contribute zero updates and zero reductions — stripped on return."""
    if interpret is None:
        interpret = not _on_tpu()
    compute_dtype = normalize_compute_dtype(compute_dtype)
    rows = U.shape[-2]
    cols = R_cols.shape[-2]
    t0 = U.shape[-1]
    U, lead = _flatten_state(U, rows, t0)
    R, _ = _flatten_state(R, rows, t0)
    D, _ = _flatten_state(D, rows, t0)
    V, _ = _flatten_state(V, rows, t0)
    R_cols, _ = _flatten_state(R_cols, cols, t0)
    D_cols, _ = _flatten_state(D_cols, cols, t0)
    V_cols, _ = _flatten_state(V_cols, cols, t0)
    b = U.shape[0]
    Xs_rows, Xs_cols = lane_aligned(Xs_rows), lane_aligned(Xs_cols)
    scalars = [
        jnp.asarray(s, jnp.float32).reshape((b, t0) if lead else (1, t0))
        for s in (alpha, beta, gamma)
    ]
    if not interpret:
        U, R, D, V = (_pad_to(a, 128, 2) for a in (U, R, D, V))
        R_cols, D_cols, V_cols = (_pad_to(a, 128, 2) for a in (R_cols, D_cols, V_cols))
        scalars = [_pad_to(s, 128, 1) for s in scalars]
    alpha, beta, gamma = scalars
    Un, Rn, Dn, Vn, red = fused_cg_step_pallas(
        Xs_rows,
        Xs_cols,
        U,
        R,
        D,
        V,
        R_cols,
        D_cols,
        V_cols,
        alpha,
        beta,
        gamma,
        jnp.asarray(outputscale),
        jnp.asarray(sigma2),
        row_offset,
        kernel_type=kernel_type,
        bn=bn,
        bm=bm,
        interpret=interpret,
        compute_dtype=compute_dtype,
        name=name,
    )
    out_shape = lead + (rows, t0)
    Un, Rn, Dn, Vn = (a[..., :t0].reshape(out_shape) for a in (Un, Rn, Dn, Vn))
    red = red[..., :t0].reshape(lead + (4, t0)) if lead else red[0, :, :t0]
    dv, rr, rv, vv = (red[..., k, :] for k in range(4))
    return Un, Rn, Dn, Vn, (dv, rr, rv, vv)


def fused_cg_step_prescaled(
    Xs,
    U,
    R,
    D,
    V,
    alpha,
    beta,
    gamma,
    outputscale,
    sigma2,
    *,
    kernel_type="rbf",
    bn=256,
    bm=512,
    interpret=None,
    compute_dtype="float32",
):
    """One fused CG iteration of K̂ = K(X, X) + σ²I for pre-scaled inputs —
    the single-device :data:`repro.core.mbcg.CGStepFn`.

    Applies the pending per-column (α, β, γ) updates to the (…, n, t) CG
    state, computes V = K̂·D tile-by-tile and returns the four per-column
    reductions [dᵀV, rᵀr, rᵀV, vᵀV] — ONE kernel launch, no XLA pass over
    the O(n·t) state.  Leading batch dims run on the native batch grid."""
    return _fused_cg_step_padded(
        Xs,
        Xs,
        U,
        R,
        D,
        V,
        R,
        D,
        V,
        alpha,
        beta,
        gamma,
        outputscale,
        sigma2,
        kernel_type=kernel_type,
        bn=bn,
        bm=bm,
        interpret=interpret,
        compute_dtype=compute_dtype,
    )


def _panel_fused_cg_step_bands(
    Xs_rows,
    Xs_cols,
    U,
    R,
    D,
    V,
    R_cols,
    D_cols,
    V_cols,
    alpha,
    beta,
    gamma,
    outputscale,
    sigma2,
    row0,
    *,
    panel_rows,
    kernel_type="rbf",
    bn=256,
    bm=512,
    interpret=None,
    compute_dtype="float32",
):
    """Panel-carried fused CG step over a contiguous row band.

    Streams the band's (…, rows, t) state through the fused kernel one
    (panel_rows × cols) launch at a time — each launch runs the full PR 4
    iteration (prologue rank-1 updates, on-the-fly direction recompute,
    epilogue reductions) for its own rows via ``row_offset = row0 + start``
    — and **carries the partial [dᵀV; rᵀr; rᵀV; vᵀV] reductions across the
    panel loop**: every panel's epilogue lands in a loop-carried (4, t)
    slab (a left fold from zeros, in panel order), so the iteration's
    reductions exist without any XLA pass over the O(rows·t) state.

    Correctness of the decomposition rests on two invariants of the fused
    kernel: (a) the prologue touches only the launch's own row block, so
    panels partition the state update exactly once per iteration; (b) the
    matmul consumes this iteration's direction recomputed on the fly from
    the *column-side* (R_cols, D_cols, V_cols) arrays — the full
    previous-iteration state, identical for every panel — so panel order
    cannot change any V row.  A non-dividing last panel runs as its own
    exact-height launch (the kernel's in-kernel row masking handles any
    height), never as zero-padded rows that would pollute vᵀV.

    ``row0`` may be traced (the sharded path passes each device's band
    start).  Returns the band's updated state and the (dv, rr, rv, vv)
    tuple of (…, t) partial sums for these rows."""
    rows = Xs_rows.shape[0]
    p = max(1, min(int(panel_rows), rows))
    num = rows // p
    rem = rows - num * p
    lead = U.shape[:-2]
    t = U.shape[-1]
    kw = dict(
        kernel_type=kernel_type,
        bn=bn,
        bm=bm,
        interpret=interpret,
        compute_dtype=compute_dtype,
        name=PANEL_FUSED_CG_STEP,
    )
    red = tuple(jnp.zeros(lead + (t,), jnp.float32) for _ in range(4))

    def one_panel(red, start):
        Xp = jax.lax.dynamic_slice_in_dim(Xs_rows, start, p, axis=0)
        bands = [
            jax.lax.dynamic_slice_in_dim(a, start, p, axis=-2)
            for a in (U, R, D, V)
        ]
        Un, Rn, Dn, Vn, pred = _fused_cg_step_padded(
            Xp, Xs_cols, *bands, R_cols, D_cols, V_cols,
            alpha, beta, gamma, outputscale, sigma2,
            row_offset=row0 + start, **kw,
        )
        red = jax.tree_util.tree_map(jnp.add, red, pred)
        return red, (Un, Rn, Dn, Vn)

    red, outs = jax.lax.scan(one_panel, red, jnp.arange(num) * p)
    state = []
    for a in outs:  # (num, …, p, t) stacked bands → (…, num·p, t)
        a = jnp.moveaxis(a, 0, -3)
        state.append(a.reshape(*a.shape[:-3], num * p, a.shape[-1]))
    if rem:
        Un, Rn, Dn, Vn, pred = _fused_cg_step_padded(
            Xs_rows[num * p :], Xs_cols,
            U[..., num * p :, :], R[..., num * p :, :],
            D[..., num * p :, :], V[..., num * p :, :],
            R_cols, D_cols, V_cols,
            alpha, beta, gamma, outputscale, sigma2,
            row_offset=row0 + num * p, **kw,
        )
        red = jax.tree_util.tree_map(jnp.add, red, pred)
        state = [
            jnp.concatenate([s, x], axis=-2)
            for s, x in zip(state, (Un, Rn, Dn, Vn))
        ]
    return state[0], state[1], state[2], state[3], red


def panel_fused_cg_step_prescaled(
    Xs,
    U,
    R,
    D,
    V,
    alpha,
    beta,
    gamma,
    outputscale,
    sigma2,
    *,
    panel_rows,
    kernel_type="rbf",
    bn=256,
    bm=512,
    interpret=None,
    compute_dtype="float32",
):
    """Partitioned fused CG iteration of K̂ = K(X, X) + σ²I — the
    single-device panel-streamed :data:`repro.core.mbcg.CGStepFn`.

    One fused-kernel launch per (panel_rows × n) row-panel instead of one
    full-range launch (whose (n × n)-bounded tile sweep is exactly the
    working set partitioning exists to break) and instead of the unfused
    loop's per-panel matmul plus ~10 XLA state passes.  The column-side
    state the kernel recomputes D from is the full pre-update (R, D, V) —
    the same arrays every panel reads — and the (4, t) reductions are
    carried across the panel loop (see :func:`_panel_fused_cg_step_bands`).
    """
    return _panel_fused_cg_step_bands(
        Xs, Xs, U, R, D, V, R, D, V,
        alpha, beta, gamma, outputscale, sigma2, 0,
        panel_rows=panel_rows, kernel_type=kernel_type,
        bn=bn, bm=bm, interpret=interpret, compute_dtype=compute_dtype,
    )


def sharded_fused_cg_step_prescaled(
    Xs,
    U,
    R,
    D,
    V,
    alpha,
    beta,
    gamma,
    outputscale,
    sigma2,
    mesh,
    axes=("data",),
    *,
    panel_rows=None,
    kernel_type="rbf",
    bn=256,
    bm=512,
    interpret=None,
    compute_dtype="float32",
):
    """Row-partitioned fused CG iteration — the sharded CGStepFn.

    Layout mirrors :func:`sharded_kernel_matmul_prescaled`: Xs replicated,
    the (…, n, t) CG state row-sharded over ``axes``.  Each device applies
    the pending updates to its own row band inside its fused kernel and
    contributes its band's partial reductions, which are summed across
    devices ONCE per iteration — the only O(t) collective.  The column-side
    (R, V, D) state is all-gathered (three payloads instead of the plain
    matmul's one: the kernel recomputes this iteration's D from them on the
    fly, which is what keeps the whole iteration a single launch per band;
    the gather stays f32 so the recursively-updated CG state never loses
    bits in flight, even when the MXU stages run at
    ``compute_dtype='bfloat16'``).

    ``panel_rows``: None runs each device band as ONE fused launch (the
    PR 4 behaviour); an int streams each device's contiguous band through
    :func:`_panel_fused_cg_step_bands` — one launch per panel, reductions
    carried across the local panel loop, then combined across devices with
    :func:`repro.distributed.sharding.ordered_psum` so the cross-device sum
    uses the same deterministic left fold as a single device scanning the
    same panels (1-device vs N-device fused solves stay bitwise-equal when
    the panel decomposition matches, i.e. when panel_rows divides the band
    height)."""
    from repro.distributed.sharding import (
        mesh_axis_sizes,
        ordered_psum,
        row_shard_spec,
        unchecked_shard_map,
    )

    compute_dtype = normalize_compute_dtype(compute_dtype)
    n = Xs.shape[0]
    sizes = mesh_axis_sizes(mesh)
    shards = 1
    for a in axes:
        shards *= sizes[a]
    if n % shards != 0:
        raise ValueError(f"n={n} must divide evenly over {shards} shards")
    row_axis = U.ndim - 2
    rep = P(*([None] * (U.ndim - 1)))  # replicated (…, t) scalar spec

    def body(Xs_full, U_loc, R_loc, D_loc, V_loc, al, be, ga, outputscale, sigma2):
        R_full = jax.lax.all_gather(R_loc, axes, axis=row_axis, tiled=True)
        D_full = jax.lax.all_gather(D_loc, axes, axis=row_axis, tiled=True)
        V_full = jax.lax.all_gather(V_loc, axes, axis=row_axis, tiled=True)
        idx = jax.lax.axis_index(axes)
        n_loc = n // shards
        X_loc = jax.lax.dynamic_slice_in_dim(Xs_full, idx * n_loc, n_loc, axis=0)
        kw = dict(
            kernel_type=kernel_type,
            bn=bn,
            bm=bm,
            interpret=interpret,
            compute_dtype=compute_dtype,
        )
        if panel_rows is not None:
            Un, Rn, Dn, Vn, red = _panel_fused_cg_step_bands(
                X_loc, Xs_full, U_loc, R_loc, D_loc, V_loc,
                R_full, D_full, V_full, al, be, ga, outputscale, sigma2,
                idx * n_loc, panel_rows=panel_rows, **kw,
            )
            red = jax.tree_util.tree_map(
                lambda x: ordered_psum(x, axes), red
            )
            return Un, Rn, Dn, Vn, red
        Un, Rn, Dn, Vn, red = _fused_cg_step_padded(
            X_loc,
            Xs_full,
            U_loc,
            R_loc,
            D_loc,
            V_loc,
            R_full,
            D_full,
            V_full,
            al,
            be,
            ga,
            outputscale,
            sigma2,
            row_offset=idx * n_loc,
            **kw,
        )
        red = jax.lax.psum(red, axes)
        return Un, Rn, Dn, Vn, red

    state_spec = row_shard_spec(U.ndim, axes)
    return unchecked_shard_map(
        body,
        mesh,
        in_specs=(
            P(None, None),
            state_spec,
            state_spec,
            state_spec,
            state_spec,
            rep,
            rep,
            rep,
            P(),
            P(),
        ),
        out_specs=(
            state_spec,
            state_spec,
            state_spec,
            state_spec,
            (rep, rep, rep, rep),
        ),
    )(
        Xs,
        U,
        R,
        D,
        V,
        jnp.asarray(alpha, jnp.float32),
        jnp.asarray(beta, jnp.float32),
        jnp.asarray(gamma, jnp.float32),
        jnp.asarray(outputscale, jnp.float32),
        jnp.asarray(sigma2, jnp.float32),
    )
