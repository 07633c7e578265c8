"""Traffic generator of the benchmark.

A copy of ``repro.data.generators.make_generator`` kept with the
benchmark, so that no later change to the program can move the traffic
it is measured on. Two things differ from the original:

- the lateness model is a real parameter (the original stores
  ``lateness_dist`` and always draws the log-normal window index): a
  traffic file names ``lnorm`` (Table 1) or ``ontime`` (nothing late);
- payload lanes that no operator reads are drawn as float32 uniform
  noise of the original's spread (std 0.02) instead of float32 normals:
  they only carry bytes through the tiers, and the uniform draw is five
  times cheaper, which keeps set-up short. The lanes an operator reads
  are drawn as in the original.

Every step draws the same number of events from one ``numpy`` generator
seeded by ``--seed``, so a seed fixes the inputs and every seed has the
same sizes and arrival schedule.
"""
from __future__ import annotations

import numpy as np

_SQRT3 = float(np.sqrt(3.0))


class Generator:
    """Batches of (keys int32 [n], timestamps float64 [n], values
    float32 [n, W]) for one configuration under one traffic mix."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.rng = np.random.default_rng(seed)
        self.operator = config["operator"]
        self.width = int(config["value_width"])
        self.num_keys = int(config["num_keys"])
        self.window = float(config["window_s"])
        self.step = float(traffic["step_s"])
        self.lateness = traffic["lateness"]

    def _keys(self, n: int) -> np.ndarray:
        """Uniform over the configuration's keys."""
        return self.rng.integers(0, self.num_keys, n).astype(np.int32)

    def _timestamps(self, n: int, now: float) -> np.ndarray:
        if self.lateness == "lnorm":
            # Table 1: ts = now - windowIndex * window - U(0, window),
            # windowIndex = floor(LogNormal(0, 1))
            widx = np.floor(self.rng.lognormal(0.0, 1.0, n))
            ts = now - widx * self.window \
                - self.rng.uniform(0, self.window, n)
        elif self.lateness == "ontime":
            # inside the stream second that just ended: never late
            ts = now - self.step + self.step * self.rng.random(n)
        else:
            raise ValueError(f"unknown lateness model {self.lateness!r}")
        return np.maximum(ts, 0.0)

    def _noise_lanes(self, n: int) -> np.ndarray:
        """[n, W] float32 of mean 1 and std 0.02 (uniform)."""
        v = self.rng.random((n, self.width), dtype=np.float32)
        v -= 0.5
        v *= np.float32(2 * _SQRT3 * 0.02)
        v += np.float32(1.0)
        return v

    def _values(self, n: int) -> np.ndarray:
        op = self.operator
        if op == "stock":
            base = self.rng.uniform(10, 500, (n, 1)).astype(np.float32)
            v = self._noise_lanes(n)
            # the price lane, as the original draws every lane
            v[:, 0] = 1 + self.rng.normal(0, 0.02, n).astype(np.float32)
            v *= base
        elif op == "lrb":
            v = np.zeros((n, self.width), np.float32)
            v[:, 0] = np.maximum(self.rng.normal(55, 20, n), 0)   # speed
            stopped = self.rng.random(n) < 0.01
            v[stopped, 0] = 0.0
            if self.width > 1:
                v[:, 1] = self.rng.integers(0, 4, n)               # lane
        else:
            raise ValueError(f"no traffic for operator {op!r}")
        return v

    def batch(self, n: int, now: float):
        ts = self._timestamps(n, now)
        keys = self._keys(n)
        return keys, ts, self._values(n)
