"""Never-spilling numpy oracles for the windowed operators.

Each oracle is a plain float64 group-by over every event it is given,
independent of the engine's blocks, tiers, pools and folds: tumbling
windows of ``window`` seconds keyed exactly as ``TumblingWindows``
assigns them. The differential soak and the on-chip smoke both hold the
engine to these.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from repro.core.windows import WindowId


def _windows(ts: np.ndarray, window: float):
    """(WindowId, event mask) per tumbling window present in ``ts``."""
    wstart = np.floor(ts / window) * window
    for s in np.unique(wstart):
        yield WindowId(float(s), float(s) + window), wstart == s


def oracle_average(keys, ts, vals, window: float) -> Dict[WindowId, float]:
    """Exact mean of value column 0 over all events of each window."""
    return {wid: float(np.mean(vals[sel, 0], dtype=np.float64))
            for wid, sel in _windows(ts, window)}


def oracle_stock(keys, ts, vals, window: float,
                 num_keys: int) -> Dict[WindowId, dict]:
    """Per-symbol min / max / mean of the price (value column 0) and the
    >=5% swing alert, for each window; keys fold modulo ``num_keys`` as
    the stock operator folds them. A symbol with no events has mean 0,
    min +inf and max -inf, the operator's fold identity."""
    out = {}
    for wid, sel in _windows(ts, window):
        k = np.asarray(keys[sel]) % num_keys
        p = np.asarray(vals[sel, 0], np.float64)
        mn = np.full(num_keys, np.inf)
        mx = np.full(num_keys, -np.inf)
        sm = np.zeros(num_keys)
        ct = np.zeros(num_keys)
        np.minimum.at(mn, k, p)
        np.maximum.at(mx, k, p)
        np.add.at(sm, k, p)
        np.add.at(ct, k, 1.0)
        with np.errstate(invalid="ignore"):
            alerts = (mx - mn) / np.where(mn > 0, mn, np.inf) >= 0.05
        out[wid] = {"mean": sm / np.maximum(ct, 1.0), "min": mn, "max": mx,
                    "alerts": alerts}
    return out
