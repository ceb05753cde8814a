"""Fused kernel-matrix matmul: (K(X,X) + σ²I) @ M without materializing K.

This is the TPU-native formulation of the paper's core primitive.  The GPU
paper materializes K in HBM once and calls cuBLAS per CG iteration; here
each (bn × bm) kernel tile is *created inside VMEM*, consumed by the MXU
against the matching (bm × t) tile of M, and never written back:

    HBM traffic   O(n·(d+t)) per row-block sweep   (vs O(n²) materialized)
    VMEM working  bn·d + bm·d + bn·bm + bm·t + bn·t
    MXU work      2·n²·(d + t) flops — compute-bound for d + t ≳ 60

Grid: (rows, cols) — col dim innermost; the (i-th, t-wide) output tile is
revisited across j and accumulated in place (classic Pallas reduction
pattern).  Distance algebra uses the ‖x‖²+‖x'‖²−2xxᵀ expansion so the MXU
does the heavy lifting; exp/Matérn polynomials run on the VPU.

Precision policy (``compute_dtype``).  ``"float32"`` (``precision=
"highest"``) is f32 accuracy on a bf16-native MXU.  An f32 value is
exactly hi + mid + lo, three bf16 pieces (``split_bf16``), and the operands
reach the kernel as those pieces, side by side in the lanes that padding to
128 would otherwise waste; each MXU stage is then a native bf16 pass
(``Precision.DEFAULT``) with f32 accumulation:

  * distances — ``ops.pack_split_operands`` packs six d-wide groups,
    [hi, hi, mid, hi, lo, mid] of the rows against [hi, mid, hi, lo, hi,
    mid] of the columns, so ONE pass over round_up(6d, 128) lanes sums
    hi·hi + hi·mid + mid·hi + hi·lo + lo·hi + mid·mid: the term set of the
    six passes ``Precision.HIGHEST`` makes over round_up(d, 128) lanes.
    The squared norms come in as f32 (rows, 1) and (1, cols) operands, and
    the diagonal is set to k(x, x) = outputscale (+ σ²) outright: norms and
    inner product summed in other orders leave a point an ulp of ‖x‖² from
    itself.
  * product — M packed as [M_hi | M_mid | M_lo] in 3t lanes; the kernel
    splits the f32 (bn, bm) tile into K_hi, K_mid, K_lo and makes three
    passes against it, and the wrapper sums the three lane groups: all nine
    cross terms, K·M at f32 accuracy in 3 passes instead of HIGHEST's 6.
    It pays while 3·⌈3t/128⌉ < 6·⌈t/128⌉, i.e. t ≤ 42; a wider M stays f32
    and takes the HIGHEST product.

A bf16 operand under ``"float32"`` therefore always carries such a split.
The fused CG step keeps its f32 operands and the HIGHEST passes.
``"bfloat16"`` (``precision="mixed"``) is a different result, not a faster
route to the same one: both stages round their operands to ONE bf16 piece
and take one pass each, which is why that policy needs mBCG's f32 residual
refresh.  Either way the VPU stages (norms, distance assembly,
exp/Matérn, the σ² diagonal and all edge masking) and the accumulator and
output stay f32.

Batched RHS is a *native grid dimension*, not a vmap: for M of shape
(b, n, t) the grid is (rows, cols, b) with the batch dim innermost, so
all b batch elements consume each (bn, d)/(bm, d) X tile while it sits in
VMEM — X tiles are fetched once per (i, j) grid tile instead of once per
(batch, i, j) as the vmapped formulation pays (``tile_load_counts`` gives
the exact accounting).  The output block spans the whole batch (b, bn, t)
so the j/b reduction stays on consecutive grid steps — the only pattern
for which Pallas guarantees in-place revisiting.

Edge handling is *in-kernel*: the grid rounds up (``pl.cdiv``) and a column
validity mask zeroes both the kernel-tile columns and the RHS rows that fall
beyond ``n_cols`` — no host-side padding of M (which would otherwise be paid
on every CG iteration), no ``n % block == 0`` restriction.  Partial edge
blocks may read unspecified values; every such value is routed through a
``jnp.where`` before it can reach the accumulator.

Row partitioning for multi-device execution: the row operand ``X1`` may be a
contiguous row-shard of the full X whose global position is given by the
dynamic ``row_offset`` operand — the σ²-diagonal is emitted at global
row == global col, so D devices can each compute their (n/D, t) slab of the
product while only the (n, t) RHS is ever all-gathered (Wang et al. 2019,
"Exact GPs on a Million Data Points").  ``row_offset`` composes with the
batch grid, so the sharded path gets batched execution for free.

Block defaults (256, 512) keep the working set ≈ (256+512)·128·4B for X
tiles + 256·512·4B for the kernel tile + M/out tiles ≈ 1.3 MB ≪ 16 MB VMEM
at t=128, and all matmul dims are multiples of the 128-lane MXU.  The
batched output block is (b, bn, t); ``bn`` is halved until it fits the
VMEM budget for large b.

Fused CG step (``fused_cg_step_pallas``): the whole mBCG iteration as ONE
grid sweep of ONE pallas_call.  The unfused loop pays, per iteration, a
kernel-matmul launch plus ~4 XLA passes over the (b, n, t) CG state
(U += αD, R −= αV, dᵀV/rᵀz reductions, D = Z + βD) — each a full HBM
round-trip of state the kernel just had in VMEM.  The fused kernel folds
all of it into the matmul sweep:

  * **prologue** (once per row block, at j == 0): the previous iteration's
    pending rank-1 updates are applied in-VMEM — U += α∘D, R −= α∘V,
    D = γ∘R + β∘D — and written through the U/R/D outputs.  γ ∈ {0, 1}
    is the direction-restart switch: γ=1 is the CG update, (α=0, β=1, γ=0)
    is the no-op prologue used right after an out-of-band f32 residual
    refresh replaced the state.
  * **matmul**: V_i += K_ij @ D_j with the *same-iteration* D recomputed
    on the fly from the (R, V, D) column tiles — the column-side copy of
    the prologue's elementwise update, recomputed per (i, j) tile so no
    grid-order hazard exists between updating D and consuming it.
  * **epilogue** (once per row block, at j == num_j−1, V_i now complete):
    the per-column reductions dᵀV, rᵀr, rᵀV, vᵀV accumulate into a
    VMEM-resident (4, t) block (constant output index map → the block
    never leaves VMEM during the sweep).  D and V are never re-read from
    HBM for the dot products; the rᵀr/rᵀV/vᵀV triplet is what lets the
    solver form the next α AND β from O(t) scalar arithmetic only
    (pipelined-CG recurrence, Ghysels & Vanroose 2014).

The α/β/γ scalars stay in XLA (O(t) work); everything O(n·t) lives in the
kernel.  Per iteration this is 1 launch instead of ≥ 2 (matmul + fused
XLA vector updates), with the state read/written exactly once —
``fused_step_tile_counts`` gives the measured tile-level accounting.
``compute_dtype`` applies to the two MXU stages exactly as above; the CG
state, its updates and the reduction accumulators are always f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.precision import as_jnp_dtype, normalize_compute_dtype

# VMEM budget for the batched (b, bn, t) f32 output block; bn is halved
# until the block fits (the X/M/kernel tiles are small next to it).
_BATCH_OUT_VMEM_BYTES = 4 * 1024 * 1024

# Stable ``pallas_call`` names: each launch's device op is named after its
# kernel, so a profiler trace finds the kernel-matrix products by the
# substring "kernel_matmul" and the fused CG steps by "cg_step" alone.
KERNEL_MATMUL = "kernel_matmul"
KERNEL_MATMUL_BATCHED = "kernel_matmul_batched"
FUSED_CG_STEP = "fused_cg_step"
PANEL_FUSED_CG_STEP = "panel_fused_cg_step"


def _mxu_precision(mxu_dtype):
    """Explicit MXU precision by operand dtype: f32 operands get HIGHEST
    (Mosaic's ``contract_precision<fp32>``, six bf16 passes on v5e: the
    fused CG step and the product for t > 42), bf16 operands one native
    pass whatever the ambient default (the mixed policy, and the packed
    bf16 splits of the ``"float32"`` policy)."""
    if mxu_dtype == jnp.float32:
        return jax.lax.Precision.HIGHEST
    return jax.lax.Precision.DEFAULT


def _apply_stationary(kernel_type: str, d2, outputscale):
    """Map squared distances → kernel values (VPU element-wise stage)."""
    if kernel_type == "rbf":
        return outputscale * jnp.exp(-0.5 * d2)
    d = jnp.sqrt(jnp.maximum(d2, 1e-20))
    if kernel_type == "matern12":
        return outputscale * jnp.exp(-d)
    if kernel_type == "matern32":
        a = jnp.sqrt(3.0) * d
        return outputscale * (1.0 + a) * jnp.exp(-a)
    if kernel_type == "matern52":
        a = jnp.sqrt(5.0) * d
        return outputscale * (1.0 + a + a * a / 3.0) * jnp.exp(-a)
    raise ValueError(kernel_type)


_BF16_HEAD = -65536  # 0xFFFF0000: sign, exponent and the 7 mantissa bits of a bf16


def _bf16_head(x, rounded):
    """x's leading bf16 piece, as f32: its top 8 significant bits, rounded
    to nearest even or truncated.  Integer arithmetic on the bits, so no
    compiler may fold it into a convert round trip."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    if rounded:
        bits = bits + (0x7FFF + ((bits >> 16) & 1))
    return jax.lax.bitcast_convert_type(bits & _BF16_HEAD, jnp.float32)


def split_bf16(x, *, rounded=True):
    """(hi, mid, lo) bf16 with hi + mid + lo == x exactly, for f32 ``x``:
    each residual is exact in f32 and each piece holds at most 8
    significant bits.  ``rounded`` pieces are the smaller (|mid| ≤ 2⁻⁸|x|,
    |lo| ≤ 2⁻¹⁶|x|), which matters where a product drops terms; truncated
    ones cost the VPU three integer ops less per piece."""
    hi = _bf16_head(x, rounded)
    r = x - hi
    mid = _bf16_head(r, rounded)
    lo = r - mid
    return tuple(p.astype(jnp.bfloat16) for p in (hi, mid, lo))


def _masked_kernel_tile(
    x1, x2, scal_ref, row_offset, i, j, *, kernel_type, bn, bm, n_cols, mxu_dtype,
    norms=None,
):
    """One (bn, bm) kernel tile: distances on the MXU (at ``mxu_dtype`` with
    f32 accumulation), stationary map + σ² diagonal + edge masking in f32.
    ``norms`` — the f32 (bn, 1) and (1, bm) squared norms — come with packed
    bf16 splits, whose lanes do not hold x itself; else the norms are
    reduced here from x."""
    outputscale = scal_ref[0]
    sigma2 = scal_ref[1]

    # ‖xi−xj‖² = ‖xi‖² + ‖xj‖² − 2⟨xi, xj⟩   (inner product on the MXU).
    # Norms are a cheap VPU reduction — keep them f32 even in mixed mode.
    if norms is None:
        x1f = x1.astype(jnp.float32)
        x2f = x2.astype(jnp.float32)
        n1 = jnp.sum(x1f * x1f, axis=-1, keepdims=True)  # (bn, 1)
        n2 = jnp.sum(x2f * x2f, axis=-1, keepdims=True).T  # (1, bm)
    else:
        n1, n2 = norms
    inner = jax.lax.dot_general(
        x1.astype(mxu_dtype),
        x2.astype(mxu_dtype),
        (((1,), (1,)), ((), ())),
        precision=_mxu_precision(mxu_dtype),
        preferred_element_type=jnp.float32,
    )
    d2 = jnp.maximum(n1 + n2 - 2.0 * inner, 0.0)

    k_tile = _apply_stationary(kernel_type, d2, outputscale)

    # global coordinates of this tile
    rows = row_offset + i * bn + jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 0)
    cols = j * bm + jax.lax.broadcasted_iota(jnp.int32, (bn, bm), 1)

    # added diagonal σ²I where global row == global col, then edge masking:
    # kernel-tile columns beyond n_cols are zeroed (kills any unspecified
    # values a partial x2 block may have produced — NaN-safe via where)
    if norms is None:
        k_tile = k_tile + jnp.where(rows == cols, sigma2, 0.0)
    else:
        # the packed inner product and the given norms are summed in other
        # orders, so a point's distance to itself cancels to an ulp of ‖x‖²
        # (not to 0), which Matérn-1/2's sqrt lifts to ~1e-3: the diagonal
        # of K(X, X) is known, k(x, x) = outputscale, so it is set
        k_tile = jnp.where(rows == cols, outputscale + sigma2, k_tile)
    return jnp.where(cols < n_cols, k_tile, 0.0)


def _tile_rhs_product(k_tile, m, j, bm, n_cols, mxu_dtype):
    """Edge-mask the RHS block and run the tile×RHS MXU stage (f32 accum).
    A bf16 ``m`` under the f32 policy is the packed [M_hi | M_mid | M_lo]:
    the tile goes in as its three bf16 pieces, one pass each."""
    m_rows = j * bm + jax.lax.broadcasted_iota(jnp.int32, m.shape, 0)
    masked = jnp.where(m_rows < n_cols, m.astype(jnp.float32), 0.0)
    if mxu_dtype == jnp.float32 and m.dtype == jnp.bfloat16:
        # the masked split is exact in bf16; its pieces' products are all
        # kept, so truncation loses nothing and spares the VPU the rounding
        m = masked.astype(jnp.bfloat16)
        hi, mid, lo = (
            jax.lax.dot_general(
                piece, m, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32,
            )
            for piece in split_bf16(k_tile, rounded=False)
        )
        return (lo + mid) + hi
    return jax.lax.dot_general(
        k_tile.astype(mxu_dtype),
        masked.astype(mxu_dtype),
        (((1,), (0,)), ((), ())),
        precision=_mxu_precision(mxu_dtype),
        preferred_element_type=jnp.float32,
    )


def _tile_partial(x1_ref, x2_ref, m, scal_ref, off_ref, norm_refs, i, j, *,
                  kernel_type, bn, bm, n_cols, mxu_dtype):
    """One grid step's (bn, t) partial product.  With ``norm_refs`` the X
    blocks are packed bf16 splits: their distance stage is one bf16 pass."""
    norms = tuple(r[...] for r in norm_refs) if norm_refs else None
    k_tile = _masked_kernel_tile(
        x1_ref[...], x2_ref[...], scal_ref, off_ref[0], i, j,
        kernel_type=kernel_type, bn=bn, bm=bm, n_cols=n_cols,
        mxu_dtype=jnp.bfloat16 if norms else mxu_dtype, norms=norms,
    )
    return _tile_rhs_product(k_tile, m, j, bm, n_cols, mxu_dtype)


def _kernel_matmul_kernel(
    off_ref,  # (1,) int32  global row offset of the X1 shard (SMEM-like)
    x1_ref,  # (bn, d)   row block of X / ℓ (or its packed split)
    x2_ref,  # (bm, d)   col block of X / ℓ (or its packed split)
    m_ref,  # (bm, t)   block of M
    scal_ref,  # (2,)    [outputscale, sigma2]
    *refs,  # [(bn, 1) row norms, (1, bm) col norms,] (bn, t) output tile
    kernel_type: str,
    bn: int,
    bm: int,
    n_cols: int,
    mxu_dtype,
):
    *norm_refs, o_ref = refs
    i, j = pl.program_id(0), pl.program_id(1)
    partial_out = _tile_partial(
        x1_ref, x2_ref, m_ref[...], scal_ref, off_ref, norm_refs, i, j,
        kernel_type=kernel_type, bn=bn, bm=bm, n_cols=n_cols, mxu_dtype=mxu_dtype,
    )

    @pl.when(j == 0)
    def _init():
        o_ref[...] = partial_out

    @pl.when(j > 0)
    def _acc():
        o_ref[...] += partial_out


def _kernel_matmul_batched_kernel(
    off_ref,  # (1,) int32
    x1_ref,  # (bn, d)   row block — shared across the batch grid dim
    x2_ref,  # (bm, d)   col block — shared across the batch grid dim
    m_ref,  # (1, bm, t) block of this batch element's M
    scal_ref,  # (2,)
    *refs,  # [(bn, 1), (1, bm) norms,] (b, bn, t) output slab (revisited over j and b)
    kernel_type: str,
    bn: int,
    bm: int,
    n_cols: int,
    mxu_dtype,
):
    """Native batch grid: grid (rows, cols, batch), batch innermost.

    The X blocks' index maps ignore the batch coordinate, so for a fixed
    (i, j) all b batch elements reuse the X tiles already resident in VMEM —
    and the kernel tile itself is recomputed per batch element (cheap next to
    the b× saving on X HBM traffic; fusing it across b would need a (bn, bm)
    scratch that outlives the batch loop, which the output slab already
    provides for the product).  The output block spans the whole batch and is
    indexed only by i, so the (j, b) reduction revisits it on consecutive
    grid steps — the supported Pallas accumulation pattern.
    """
    *norm_refs, o_ref = refs
    i, j, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    partial_out = _tile_partial(
        x1_ref, x2_ref, m_ref[0], scal_ref, off_ref, norm_refs, i, j,
        kernel_type=kernel_type, bn=bn, bm=bm, n_cols=n_cols, mxu_dtype=mxu_dtype,
    )

    sl = pl.dslice(b, 1)

    @pl.when(j == 0)
    def _init():
        o_ref[sl] = partial_out[None]

    @pl.when(j > 0)
    def _acc():
        o_ref[sl] += partial_out[None]


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def _effective_blocks(
    rows: int, cols: int, t: int, batch: int | None, bn: int, bm: int,
    slabs: int = 1,
):
    """The block sizes the kernel will actually run with: clamped to the
    (sublane-aligned) problem size, and — batched — halved until the
    (b, bn, t) f32 output slab fits the VMEM budget.  ``slabs`` counts the
    number of (b, bn, t) VMEM-resident state blocks the kernel holds (1 for
    the plain matmul's output; 8 for the fused CG step's four state inputs
    plus four state outputs)."""
    bn = min(bn, _round_up(rows, 8))
    bm = min(bm, _round_up(cols, 8))
    if batch is not None:
        while slabs * batch * bn * t * 4 > _BATCH_OUT_VMEM_BYTES and bn > 8:
            bn = _round_up(bn // 2, 8)
        if slabs * batch * bn * t * 4 > 4 * _BATCH_OUT_VMEM_BYTES:
            # even bn=8 can't fit the (b, bn, t) output slab in VMEM —
            # fail loudly instead of letting Mosaic die opaquely
            raise ValueError(
                f"batched kernel matmul: batch={batch} × t={t} × {slabs} "
                f"state slab(s) exceed the VMEM budget even at bn=8; split "
                f"the batch into chunks (e.g. lax.map over "
                f"≤{4 * _BATCH_OUT_VMEM_BYTES // (slabs * 8 * t * 4)}"
                f"-element groups) or reduce t"
            )
    return bn, bm


def tile_load_counts(
    rows: int, cols: int, batch: int, *, t: int = 128, bn: int = 256, bm: int = 512
) -> dict:
    """Analytic X-tile HBM-load accounting: native batch grid vs vmap.

    Mirrors the index maps above: per batch sweep the (bn, d) row tile is
    fetched once per i (it only changes when i does) and the (bm, d) column
    tile once per (i, j).  The vmapped formulation pays that b times; the
    native grid's X index maps ignore the batch coordinate, so it pays once.
    """
    ebn, ebm = _effective_blocks(rows, cols, t, batch, bn, bm)
    gi, gj = pl.cdiv(rows, ebn), pl.cdiv(cols, ebm)
    per_sweep = gi + gi * gj  # x1 loads + x2 loads for one (i, j) sweep
    return {
        "grid": (gi, gj, batch),
        "native_x_tile_loads": per_sweep,
        "vmapped_x_tile_loads": batch * per_sweep,
        "x_load_ratio": batch,  # == vmapped / native by construction
    }


def kernel_matmul_pallas(
    X1: jax.Array,  # (rows, d) row shard, pre-divided by lengthscale
    X2: jax.Array,  # (cols, d) full column inputs, pre-divided by lengthscale
    M: jax.Array,  # (cols, t) or (b, cols, t)
    outputscale: jax.Array,
    sigma2: jax.Array,
    row_offset: jax.Array | int = 0,  # global row index of X1[0]
    *,
    kernel_type: str = "rbf",
    bn: int = 256,
    bm: int = 512,
    interpret: bool = False,
    compute_dtype: str = "float32",
    norms: tuple[jax.Array, jax.Array] | None = None,
) -> jax.Array:
    """(K(X1, X2) + σ²I_global) @ M → (rows, t) or (b, rows, t), edge-masked
    in kernel.  ``compute_dtype="bfloat16"`` runs the MXU stages in bf16 with
    f32 accumulation; the output is always f32.  A 3-dim M takes the native
    batch grid (one pallas_call, X tiles shared across the batch).

    ``norms`` = (‖X1‖² as (rows, 1), ‖X2‖² as (1, cols)), f32, marks X1 and
    X2 as the packed bf16 splits of the distance stage; a bf16 M under
    ``"float32"`` is the packed split of the product (module docstring).
    The output then holds one lane group per piece of M, for the caller to
    sum."""
    batched = M.ndim == 3
    rows, d = X1.shape
    cols, t = M.shape[-2:]
    assert X2.shape[0] == cols, (X2.shape, M.shape)
    mxu_dtype = as_jnp_dtype(compute_dtype)

    batch = M.shape[0] if batched else None
    bn, bm = _effective_blocks(rows, cols, t, batch, bn, bm)

    scal = jnp.stack([outputscale.astype(jnp.float32), sigma2.astype(jnp.float32)])
    off = jnp.asarray(row_offset, jnp.int32).reshape(1)
    norm_args = () if norms is None else tuple(jnp.asarray(v, jnp.float32) for v in norms)

    common = dict(kernel_type=kernel_type, bn=bn, bm=bm, n_cols=cols, mxu_dtype=mxu_dtype)
    if batched:
        grid = (pl.cdiv(rows, bn), pl.cdiv(cols, bm), batch)
        norm_specs = [
            pl.BlockSpec((bn, 1), lambda i, j, b: (i, 0)),
            pl.BlockSpec((1, bm), lambda i, j, b: (0, j)),
        ]
        return pl.pallas_call(
            functools.partial(_kernel_matmul_batched_kernel, **common),
            grid=grid,
            in_specs=[
                pl.BlockSpec((1,), lambda i, j, b: (0,)),
                pl.BlockSpec((bn, d), lambda i, j, b: (i, 0)),
                pl.BlockSpec((bm, d), lambda i, j, b: (j, 0)),
                pl.BlockSpec((1, bm, t), lambda i, j, b: (b, j, 0)),
                pl.BlockSpec((2,), lambda i, j, b: (0,)),
            ] + norm_specs[: len(norm_args)],
            out_specs=pl.BlockSpec((batch, bn, t), lambda i, j, b: (0, i, 0)),
            out_shape=jax.ShapeDtypeStruct((batch, rows, t), jnp.float32),
            interpret=interpret,
            name=KERNEL_MATMUL_BATCHED,
        )(off, X1, X2, M, scal, *norm_args)

    grid = (pl.cdiv(rows, bn), pl.cdiv(cols, bm))
    norm_specs = [
        pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
        pl.BlockSpec((1, bm), lambda i, j: (0, j)),
    ]
    return pl.pallas_call(
        functools.partial(_kernel_matmul_kernel, **common),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i, j: (0,)),
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bm, d), lambda i, j: (j, 0)),
            pl.BlockSpec((bm, t), lambda i, j: (j, 0)),
            pl.BlockSpec((2,), lambda i, j: (0,)),
        ] + norm_specs[: len(norm_args)],
        out_specs=pl.BlockSpec((bn, t), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, t), jnp.float32),
        interpret=interpret,
        name=KERNEL_MATMUL,
    )(off, X1, X2, M, scal, *norm_args)


# ---------------------------------------------------------------------------
# Fused CG step: one pallas_call per mBCG iteration
# ---------------------------------------------------------------------------

# number of (b, bn, t) f32 state blocks the fused kernel keeps in VMEM at
# once: U/R/D/V inputs + U/R/D/V outputs (the (b, 4, t) reduction
# accumulator and the (bm, t) column tiles are small next to them)
_FUSED_STATE_SLABS = 8


def _fused_cg_step_kernel(
    off_ref,  # (1,) int32   global row offset of the X1 shard
    x1_ref,  # (bn, d)    row block of X/ℓ
    x2_ref,  # (bm, d)    col block of X/ℓ
    rcol_ref,  # (1, bm, t)  col block of the previous residual R
    dcol_ref,  # (1, bm, t)  col block of the previous direction D
    vcol_ref,  # (1, bm, t)  col block of the previous product V = K̂D
    urow_ref,  # (batch, bn, t) row block of the previous solve U
    rrow_ref,  # (batch, bn, t) row block of the previous residual R
    drow_ref,  # (batch, bn, t) row block of the previous direction D
    vrow_ref,  # (batch, bn, t) row block of the previous product V
    scal_ref,  # (2,)       [outputscale, sigma2]
    ab_ref,  # (1, 3, t)    [α; β; γ] per-column step scalars
    uo_ref,  # (batch, bn, t) updated U
    ro_ref,  # (batch, bn, t) updated R
    do_ref,  # (batch, bn, t) updated D
    vo_ref,  # (batch, bn, t) V = (K+σ²I) @ D_updated  (revisited over j, b)
    red_ref,  # (batch, 4, t)  [dᵀV; rᵀr; rᵀV; vᵀV] accumulator (VMEM-resident)
    *,
    kernel_type: str,
    bn: int,
    bm: int,
    n_rows: int,
    n_cols: int,
    num_j: int,
    mxu_dtype,
):
    """One grid step of the fused CG iteration (see module docstring).

    Grid (rows, cols, batch), batch innermost.  All state arithmetic is
    f32 on the VPU; only the kernel-tile distances and the tile×D product
    take ``mxu_dtype`` operands (f32 accumulation).  The column-side D is
    recomputed from the (R, V, D) column tiles per (i, j) step — the
    elementwise twin of the prologue update, so the matmul always consumes
    this iteration's direction without any write-then-read hazard across
    grid steps.
    """
    i, j, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    alpha = ab_ref[0, 0]  # (t,) previous step size (0 on the first step)
    beta = ab_ref[0, 1]  # (t,) previous momentum
    gamma = ab_ref[0, 2]  # (t,) direction-restart switch (1 = CG update)

    # column-side state advance: D_new = γ∘(R − α∘V) + β∘D  (f32, VPU)
    rcol = rcol_ref[0] - alpha[None, :] * vcol_ref[0]
    dcol = gamma[None, :] * rcol + beta[None, :] * dcol_ref[0]
    # NaN hygiene for partial edge blocks: rows of D beyond n_cols are
    # unspecified-input arithmetic — zero them before the MXU sees them
    col_ids = j * bm + jax.lax.broadcasted_iota(jnp.int32, dcol.shape, 0)
    dcol = jnp.where(col_ids < n_cols, dcol, 0.0)

    k_tile = _masked_kernel_tile(
        x1_ref[...], x2_ref[...], scal_ref, off_ref[0], i, j,
        kernel_type=kernel_type, bn=bn, bm=bm, n_cols=n_cols, mxu_dtype=mxu_dtype,
    )
    partial_out = jax.lax.dot_general(
        k_tile.astype(mxu_dtype),
        dcol.astype(mxu_dtype),
        (((1,), (0,)), ((), ())),
        precision=_mxu_precision(mxu_dtype),
        preferred_element_type=jnp.float32,
    )

    sl = pl.dslice(b, 1)

    @pl.when(j == 0)
    def _prologue():
        # apply the pending rank-1 updates of the previous iteration to this
        # row block, once per (i, b) — U/R/D leave through the outputs
        u = urow_ref[sl][0]
        r = rrow_ref[sl][0]
        d = drow_ref[sl][0]
        v = vrow_ref[sl][0]
        rn = r - alpha[None, :] * v
        uo_ref[sl] = (u + alpha[None, :] * d)[None]
        ro_ref[sl] = rn[None]
        do_ref[sl] = (gamma[None, :] * rn + beta[None, :] * d)[None]
        vo_ref[sl] = partial_out[None]

    @pl.when(j > 0)
    def _acc():
        vo_ref[sl] += partial_out[None]

    @pl.when((i == 0) & (j == 0) & (b == 0))
    def _init_reductions():
        red_ref[...] = jnp.zeros_like(red_ref)

    @pl.when(j == num_j - 1)
    def _epilogue():
        # V_i is complete for this (i, b): fold the row block's contribution
        # to the four per-column reductions while everything is in VMEM.
        # The updated R/D are recomputed from the (still-resident) input
        # blocks — cheaper than carrying scratch across grid steps.
        v_full = vo_ref[sl][0]
        r = rrow_ref[sl][0]
        d = drow_ref[sl][0]
        v_prev = vrow_ref[sl][0]
        rn = r - alpha[None, :] * v_prev
        dn = gamma[None, :] * rn + beta[None, :] * d
        valid = (
            i * bn + jax.lax.broadcasted_iota(jnp.int32, v_full.shape, 0)
        ) < n_rows
        vm = jnp.where(valid, v_full, 0.0)
        rm = jnp.where(valid, rn, 0.0)
        dm = jnp.where(valid, dn, 0.0)
        red = jnp.stack(
            [
                jnp.sum(dm * vm, axis=0),  # dᵀV   → α denominator
                jnp.sum(rm * rm, axis=0),  # rᵀr   → rz (exact, measured)
                jnp.sum(rm * vm, axis=0),  # rᵀV   → pipelined rz recurrence
                jnp.sum(vm * vm, axis=0),  # vᵀV   → pipelined rz recurrence
            ]
        )
        red_ref[sl] += red[None]


def fused_cg_step_pallas(
    X1: jax.Array,  # (rows, d) row shard, pre-divided by lengthscale
    X2: jax.Array,  # (cols, d) full column inputs, pre-divided by lengthscale
    U: jax.Array,  # (b, rows, t) CG state — this shard's rows
    R: jax.Array,  # (b, rows, t)
    D: jax.Array,  # (b, rows, t)
    V: jax.Array,  # (b, rows, t)
    R_cols: jax.Array,  # (b, cols, t) full-column view of R (same array
    D_cols: jax.Array,  # (b, cols, t)  single-device; the all-gathered state
    V_cols: jax.Array,  # (b, cols, t)  on the row-sharded path)
    alpha: jax.Array,  # (b, t) previous step sizes
    beta: jax.Array,  # (b, t) previous momenta
    gamma: jax.Array,  # (b, t) direction-restart switch
    outputscale: jax.Array,
    sigma2: jax.Array,
    row_offset: jax.Array | int = 0,
    *,
    kernel_type: str = "rbf",
    bn: int = 256,
    bm: int = 512,
    interpret: bool = False,
    compute_dtype: str = "float32",
    name: str = FUSED_CG_STEP,
):
    """One fused CG iteration of K̂ = K(X, X) + σ²I: applies the pending
    (α, β, γ) state updates, computes V = K̂·D_new tile-by-tile, and
    accumulates the per-column reductions — all in ONE pallas_call.

    Returns ``(U, R, D, V, red)`` with ``red`` of shape (b, 4, t) holding
    [dᵀV; rᵀr; rᵀV; vᵀV].  All outputs are f32; ``compute_dtype`` selects
    the MXU operand dtype only (see module docstring).
    """
    rows, d = X1.shape
    cols = X2.shape[0]
    batch, _, t = U.shape
    assert R_cols.shape[-2] == cols, (R_cols.shape, X2.shape)
    mxu_dtype = as_jnp_dtype(compute_dtype)
    bn, bm = _effective_blocks(rows, cols, t, batch, bn, bm, slabs=_FUSED_STATE_SLABS)
    num_j = pl.cdiv(cols, bm)

    scal = jnp.stack([outputscale.astype(jnp.float32), sigma2.astype(jnp.float32)])
    off = jnp.asarray(row_offset, jnp.int32).reshape(1)
    ab = jnp.stack([alpha, beta, gamma], axis=1).astype(jnp.float32)  # (b, 3, t)

    grid = (pl.cdiv(rows, bn), pl.cdiv(cols, bm), batch)
    state_spec = pl.BlockSpec((batch, bn, t), lambda i, j, b: (0, i, 0))
    col_spec = pl.BlockSpec((1, bm, t), lambda i, j, b: (b, j, 0))
    state_shape = jax.ShapeDtypeStruct((batch, rows, t), jnp.float32)
    return pl.pallas_call(
        functools.partial(
            _fused_cg_step_kernel,
            kernel_type=kernel_type,
            bn=bn,
            bm=bm,
            n_rows=rows,
            n_cols=cols,
            num_j=num_j,
            mxu_dtype=mxu_dtype,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda i, j, b: (0,)),
            pl.BlockSpec((bn, d), lambda i, j, b: (i, 0)),
            pl.BlockSpec((bm, d), lambda i, j, b: (j, 0)),
            col_spec,
            col_spec,
            col_spec,
            state_spec,
            state_spec,
            state_spec,
            state_spec,
            pl.BlockSpec((2,), lambda i, j, b: (0,)),
            pl.BlockSpec((1, 3, t), lambda i, j, b: (b, 0, 0)),
        ],
        out_specs=[
            state_spec,
            state_spec,
            state_spec,
            state_spec,
            pl.BlockSpec((batch, 4, t), lambda i, j, b: (0, 0, 0)),
        ],
        out_shape=[
            state_shape,
            state_shape,
            state_shape,
            state_shape,
            jax.ShapeDtypeStruct((batch, 4, t), jnp.float32),
        ],
        interpret=interpret,
        name=name,
    )(off, X1, X2, R_cols, D_cols, V_cols, U, R, D, V, scal, ab)


def fused_step_tile_counts(
    rows: int,
    cols: int,
    batch: int,
    *,
    t: int = 128,
    bn: int = 256,
    bm: int = 512,
    panel_rows: int | None = None,
) -> dict:
    """Measured tile-level HBM traffic of ONE fused CG iteration, mirrored
    from the index maps of ``_fused_cg_step_kernel`` (the same way
    ``tile_load_counts`` mirrors the plain matmul) — including the
    fused-epilogue passes, which cost ZERO extra loads: the epilogue reads
    the (batch, bn, t) row blocks that are already VMEM-resident for the
    prologue, and the (4, t) accumulator has a constant index map so it
    never round-trips HBM during the sweep.

    Returns tile counts and modeled f32 HBM bytes per iteration for the
    fused kernel vs the unfused path (pallas matmul + XLA state updates,
    which re-reads/re-writes the (b, n, t) state ~4 more times per
    iteration and launches ≥ 2 programs).

    Regime note the model makes visible: the fused kernel reads THREE
    column-state arrays per row-block sweep (it recomputes this
    iteration's D from (R, V, D) on the fly) where the plain matmul reads
    one, so fused traffic is (3·gi + 8)·n·t·4B vs the unfused
    (gi + 13)·n·t·4B — the byte win holds for gi ≲ 2 row blocks, i.e.
    exactly the per-device partition sizes of the sharded exact-GP regime
    the fusion targets (n_loc ≲ 2·bn).  Above that the fused path still
    wins on launches (1 vs ≥ 2 + the XLA pass dispatch latencies), just
    not on raw bytes.

    ``panel_rows`` models the PANEL-FUSED partitioned step instead: the
    fused kernel launched once per (panel_rows × cols) row-panel with the
    (4, t) reductions carried across the panel loop (a non-dividing tail
    runs as one exact-height launch).  Counts are the sum of the
    per-height sub-launches; ``launches_per_iter_fused == num_panels``
    (vs the unfused partitioned iteration's ``num_panels`` matmul
    launches PLUS one full-height set of XLA state passes), and the
    returned dict gains ``num_panels`` / ``panel_rows`` keys.
    """
    if panel_rows is not None:
        p = max(1, min(int(panel_rows), rows))
        num = rows // p
        rem = rows - num * p
        heights = [p] * num + ([rem] if rem else [])
        subs = [
            fused_step_tile_counts(h, cols, batch, t=t, bn=bn, bm=bm)
            for h in heights
        ]
        d_bytes = 4
        nt = rows * t * batch
        fused_bytes = sum(s["fused_hbm_bytes_per_iter"] for s in subs)
        # unfused partitioned iteration: each panel's matmul traffic (D
        # column tiles + its V rows), then ONE full-height set of XLA
        # state-update passes — strip each sub-model's own XLA component
        # and add the 12 (b, n, t) passes once
        unfused_bytes = (
            sum(
                s["unfused_hbm_bytes_per_iter"] - 12 * h * t * batch * d_bytes
                for s, h in zip(subs, heights)
            )
            + 12 * nt * d_bytes
        )
        return {
            "grid": subs[0]["grid"],
            "num_panels": len(heights),
            "panel_rows": p,
            "x_tile_loads": sum(s["x_tile_loads"] for s in subs),
            "col_state_tile_loads": sum(s["col_state_tile_loads"] for s in subs),
            "row_state_tile_loads": sum(s["row_state_tile_loads"] for s in subs),
            "epilogue_extra_tile_loads": 0,
            "state_slab_stores": sum(s["state_slab_stores"] for s in subs),
            "fused_hbm_bytes_per_iter": fused_bytes,
            "unfused_hbm_bytes_per_iter": unfused_bytes,
            "hbm_bytes_ratio": unfused_bytes / fused_bytes,
            "launches_per_iter_fused": len(heights),
            "launches_per_iter_unfused": len(heights) + 1,
        }
    ebn, ebm = _effective_blocks(
        rows, cols, t, batch, bn, bm, slabs=_FUSED_STATE_SLABS
    )
    gi, gj = pl.cdiv(rows, ebn), pl.cdiv(cols, ebm)
    x_tile_loads = gi + gi * gj  # x1 once per i; x2 once per (i, j)
    # column state tiles (R, V, D): block index (b, j) → fetched per (i, j, b)
    col_state_tiles = 3 * gi * gj * batch
    # row state slabs (U, R, D, V in): block index i only → fetched once per
    # i, shared across the whole (j, b) sweep AND between prologue/epilogue
    row_state_tiles = 4 * gi
    # outputs: U/R/D/V written once per row block; the reduction accumulator
    # writes back once at the end of the sweep
    out_state_tiles = 4 * gi
    d_bytes = 4  # f32 state
    nt = rows * t * batch
    fused_bytes = (
        col_state_tiles * ebm * t * d_bytes
        + row_state_tiles * batch * ebn * t * d_bytes
        + out_state_tiles * batch * ebn * t * d_bytes
        + 4 * batch * t * d_bytes
    )
    # unfused iteration: the pallas matmul reads the D column tiles (1 array
    # instead of 3) and writes V; the XLA vector stage then pays full
    # (b, n, t) passes for dᵀV (read D, V), U += αD (read U, D, write U),
    # R −= αV (read R, V, write R), rᵀz (read R) and D = Z + βD (read R, D,
    # write D): 9 reads + 3 writes of the state per iteration.
    unfused_bytes = (
        gi * gj * batch * ebm * t * d_bytes  # matmul D tiles
        + nt * d_bytes  # matmul V write
        + 12 * nt * d_bytes  # XLA update/reduction passes
    )
    return {
        "grid": (gi, gj, batch),
        "x_tile_loads": x_tile_loads,
        "col_state_tile_loads": col_state_tiles,
        "row_state_tile_loads": row_state_tiles,
        "epilogue_extra_tile_loads": 0,  # reductions reuse resident blocks
        "state_slab_stores": out_state_tiles,
        "fused_hbm_bytes_per_iter": fused_bytes,
        "unfused_hbm_bytes_per_iter": unfused_bytes,
        "hbm_bytes_ratio": unfused_bytes / fused_bytes,
        "launches_per_iter_fused": 1,
        "launches_per_iter_unfused": 2,  # kernel matmul + fused XLA update
    }
