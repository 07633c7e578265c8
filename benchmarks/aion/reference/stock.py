"""Plain float64 reference of the stock-market operator.

Per symbol: min, max and mean of the price (value lane 0) and the alert
for a swing of 5% or more, over every event of a tumbling window. This
is ``repro.testing.oracles.oracle_stock``, copied here so that no later
change to the program can move it, and kept as running per-window
accumulators so that the state at each emission is read in one pass
over the stream. It imports nothing of the program.

``dtype`` selects the arithmetic and ``values`` the type the prices are
rounded to first: float64 throughout is the reference. The controls run
the same code in bfloat16, the precision below the float32 that the
configuration states, or in float32 over prices stored in bfloat16.
"""
from __future__ import annotations

import numpy as np

#: the value lanes the operator reads
COLUMNS = 1

#: the numbers compared, each with its limit (PERF.md gives the readings
#: they were set from)
LIMITS = {
    # float32 sums on the device against float64: sound runs read at
    # most 2.7e-6, the bfloat16 control at least 0.78; the limit sits
    # past the middle of the two (in log terms), nearer the control
    "mean_rel_err": 3e-3,
    # min and max are float32 inputs, so any gap is a wrong event; a
    # symbol present on one side only reads inf
    "minmax_rel_err": 0.0,
    "alert_mismatches": 0,
}


class Window:
    """Running aggregates of one window."""

    def __init__(self, num_keys: int, dtype=np.float64, values=None):
        self.num_keys = num_keys
        self.dtype = dtype
        self.values = values
        self.mn = np.full(num_keys, np.inf, dtype)
        self.mx = np.full(num_keys, -np.inf, dtype)
        self.sm = np.zeros(num_keys, dtype)
        # counts in the arithmetic's own type too: the engine counts in
        # float32, and a bfloat16 count stops at 256
        self.ct = np.zeros(num_keys, np.int64 if dtype == np.float64
                           else dtype)

    def add(self, keys: np.ndarray, vals: np.ndarray) -> None:
        k = np.asarray(keys) % self.num_keys
        p = np.asarray(vals[:, 0])
        if self.values is not None:
            p = p.astype(self.values)
        p = p.astype(self.dtype)
        np.minimum.at(self.mn, k, p)
        np.maximum.at(self.mx, k, p)
        if self.dtype == np.float64:
            self.sm += np.bincount(k, weights=p, minlength=self.num_keys)
            self.ct += np.bincount(k, minlength=self.num_keys)
        else:
            np.add.at(self.sm, k, p)
            np.add.at(self.ct, k, np.ones(len(k), self.dtype))

    def result(self) -> dict:
        mn = self.mn.astype(np.float64)
        mx = self.mx.astype(np.float64)
        mean = (self.sm / np.maximum(self.ct, 1).astype(self.dtype)
                ).astype(np.float64)
        mean[self.ct == 0] = 0.0
        with np.errstate(invalid="ignore"):
            alerts = (mx - mn) / np.where(mn > 0, mn, np.inf) >= 0.05
        return {"mean": mean, "min": mn, "max": mx, "alerts": alerts}


def _gap(got, want) -> float:
    """Widest relative gap; equal values (infinities too) read 0 and a
    value infinite on one side only reads inf."""
    g = np.asarray(got, np.float64)
    same = g == want
    with np.errstate(invalid="ignore", divide="ignore"):
        err = np.abs(g - want) / np.abs(want)
    err = np.where(same, 0.0, err)
    return float(np.max(np.nan_to_num(err, nan=np.inf), initial=0.0))


def compare(got: dict, want: dict) -> dict:
    """The numbers of one emission, by name (see ``LIMITS``)."""
    present = np.isfinite(want["min"])
    g = np.asarray(got["mean"], np.float64)
    err = np.abs(g[present] - want["mean"][present]) \
        / np.maximum(np.abs(want["mean"][present]), 1e-30)
    return {
        "mean_rel_err": float(np.max(err, initial=0.0)),
        "minmax_rel_err": max(_gap(got["min"], want["min"]),
                              _gap(got["max"], want["max"])),
        "alert_mismatches": int(np.sum(
            np.asarray(got["alerts"])[present] != want["alerts"][present])),
    }
