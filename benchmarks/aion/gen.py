"""Traffic generator of the benchmark.

A copy of ``repro.data.generators.make_generator`` kept with the
benchmark, so that no later change to the program can move the traffic
it is measured on. The lateness model is a real parameter here (the
original stores ``lateness_dist`` and always draws the log-normal window
index): a traffic file names ``lnorm`` (Table 1) or ``ontime`` (nothing
late). What an event carries belongs to the configuration's operator:
``events/<operator>.py`` draws its keys and value lanes
(``draw(rng, n, config)``), found by name as ``harness.load_cell`` finds
the reference.

Every step draws the same number of events from one ``numpy`` generator
seeded by ``--seed``, timestamps first, then keys, then values, so a
seed fixes the inputs and every seed has the same sizes and arrival
schedule.
"""
from __future__ import annotations

import numpy as np


class Generator:
    """Batches of (keys int32 [n], timestamps float64 [n], values
    float32 [n, W]) for one configuration under one traffic mix;
    ``events`` is the operator's ``events/<operator>.py``."""

    def __init__(self, config: dict, traffic: dict, seed: int, events):
        self.rng = np.random.default_rng(seed)
        self.config = config
        self.draw = events.draw
        self.window = float(config["window_s"])
        self.step = float(traffic["step_s"])
        self.lateness = traffic["lateness"]

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

    def batch(self, n: int, now: float):
        ts = self._timestamps(n, now)
        keys, values = self.draw(self.rng, n, self.config)
        return keys, ts, values
