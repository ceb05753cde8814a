"""Seeded inputs: the synthetic UCI-like regression scheme (the
``RegressionStream`` "smooth" scheme, copied so the benchmark owns it)."""

from __future__ import annotations

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per (seed, purpose); any non-negative seed."""
    return np.random.default_rng([stream, seed])


def regression(n: int, d: int, seed: int, noise: float = 0.1):
    """X uniform on [0, 1]^d; y = sin(4 Xw) + 0.4 cos(7 x_0) + noise,
    standardised.  float32 host arrays."""
    g = rng(seed, 0)
    X = g.uniform(0.0, 1.0, (n, d)).astype(np.float32)
    w = g.normal(size=(d,)).astype(np.float32)
    y = np.sin(4.0 * (X @ w)) + 0.4 * np.cos(7.0 * X[:, 0])
    y = y + noise * g.normal(size=(n,)).astype(np.float32)
    y = (y - y.mean()) / y.std()
    return X, y.astype(np.float32)

