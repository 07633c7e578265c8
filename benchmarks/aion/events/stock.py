"""Events of the stock-market operator: uniform symbols, and per event a
base price U(10, 500) times lanes of mean 1. The price lane (lane 0) is
drawn as ``repro.data.generators.make_generator`` draws every lane,
1 + N(0, 0.02); the lanes no operator reads are float32 uniform noise of
the same spread, which only carries bytes through the tiers and is five
times cheaper to draw."""
from __future__ import annotations

import numpy as np

_SQRT3 = float(np.sqrt(3.0))


def draw(rng: np.random.Generator, n: int, config: dict):
    """(keys int32 [n], values float32 [n, W]) from ``rng``."""
    keys = rng.integers(0, int(config["num_keys"]), n).astype(np.int32)
    base = rng.uniform(10, 500, (n, 1)).astype(np.float32)
    # [n, W] float32 of mean 1 and std 0.02 (uniform)
    v = rng.random((n, int(config["value_width"])), dtype=np.float32)
    v -= 0.5
    v *= np.float32(2 * _SQRT3 * 0.02)
    v += np.float32(1.0)
    v[:, 0] = 1 + rng.normal(0, 0.02, n).astype(np.float32)
    v *= base
    return keys, v
