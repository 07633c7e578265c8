"""Events of the Linear Road operator: position reports from uniform
road segments, lane 0 the speed (N(55, 20) clipped at 0, 1% of cars
stopped), lane 1 the lane (0-3), as
``repro.data.generators.make_generator`` draws them; the other lanes
are zero."""
from __future__ import annotations

import numpy as np


def draw(rng: np.random.Generator, n: int, config: dict):
    """(keys int32 [n], values float32 [n, W]) from ``rng``."""
    width = int(config["value_width"])
    keys = rng.integers(0, int(config["num_keys"]), n).astype(np.int32)
    v = np.zeros((n, width), np.float32)
    v[:, 0] = np.maximum(rng.normal(55, 20, n), 0)      # speed
    stopped = rng.random(n) < 0.01
    v[stopped, 0] = 0.0
    if width > 1:
        v[:, 1] = rng.integers(0, 4, n)                  # lane
    return keys, v
