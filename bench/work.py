"""Required work of the exact-GP path, counted from the real, unpadded
shapes (n rows, d inputs, t right-hand sides).  Padding the kernel does to
fill 128 lanes is not work: removing it reads as a higher share.

Per pair of rows (x_i, x_j):
  distance   2d + 3   (d multiply-adds of the scaled inputs, then
                       |x_i|^2 + |x_j|^2 - 2 x_i.x_j)
  Matern-5/2 9        (sqrt, scale, square, /3, two adds, exp, product,
                       outputscale; a transcendental counts as one)
  product    2t       (one multiply-add per right-hand side)
Backward per pair (recomputed distances and kernel values not counted):
  2t   (cotangent . right-hand sides), 6 (the kernel's derivative),
  3d   ((x_ik - x_jk)^2 times the weight, summed, per lengthscale).
"""

from __future__ import annotations

DIST_PER_PAIR = lambda d: 2 * d + 3  # noqa: E731
MATERN52_PER_PAIR = 9
BWD_KERNEL_PER_PAIR = 6
F32_BYTES = 4


def kernel_matmul_flops(rows: int, cols: int, d: int, t: int) -> float:
    """One (K(X1, X2) + s2 I) @ M product."""
    return float(rows) * cols * (DIST_PER_PAIR(d) + MATERN52_PER_PAIR + 2 * t)


def kernel_matmul_bytes(rows: int, cols: int, d: int, t: int) -> float:
    """Least HBM traffic of one product: X1, X2 and M read once, out written."""
    return float(F32_BYTES) * (rows * d + cols * d + cols * t + rows * t)


def kernel_matmul_min_s(rows, cols, d, t, peak_flops, peak_bytes) -> tuple[float, str]:
    """Least time the chip could take for one product, and which bound."""
    f = kernel_matmul_flops(rows, cols, d, t) / peak_flops
    b = kernel_matmul_bytes(rows, cols, d, t) / peak_bytes
    return (f, "flops") if f >= b else (b, "bytes")


def backward_flops(n: int, d: int, t: int) -> float:
    """Gradient contractions over the hyperparameters for one MLL step."""
    return float(n) * n * (2 * t + BWD_KERNEL_PER_PAIR + 3 * d)


def train_step_flops(n: int, d: int, t: int, cg_iters: float) -> float:
    """One MLL training step: ``cg_iters`` kernel products in mBCG plus the
    backward.  Preconditioner, CG vector updates and SLQ are O(n t) or
    O(n k) per iteration and left out (under 1% at these sizes)."""
    return cg_iters * kernel_matmul_flops(n, n, d, t) + backward_flops(n, d, t)
