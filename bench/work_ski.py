"""Required work of the deep-kernel KISS-GP step, counted from the real,
unpadded shapes: n rows, q interpolation weights per row (4 per grid
dimension), m grid points, t right-hand sides.

One application of the interpolation (W @ V or W' @ M) must at least read
W's indices and values (int32 and f32, n x q each), gather or scatter n q
rows of t values, and read its operand and write its result ((n + m) t
values), all 4 bytes wide:

  bytes = 4 (2 n q + n q t + (n + m) t)

The feature net's matmuls: 2 a b FLOPs per row and layer of widths a -> b
forward, twice that backward (input and weight gradients), so 6 n sum(a b)
per step.  Biases, ReLU and ScaleToBounds are left out.
"""

from __future__ import annotations

F32_BYTES = 4


def interp_bytes(n: int, m: int, q: int, t: int) -> float:
    """Least HBM traffic of one interpolation application."""
    return float(F32_BYTES) * (2 * n * q + n * q * t + (n + m) * t)


def features_flops(n: int, widths) -> float:
    """The feature net's forward and backward matmuls over n rows, one step."""
    return 6.0 * n * sum(a * b for a, b in zip(widths[:-1], widths[1:]))
