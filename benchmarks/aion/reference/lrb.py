"""Plain float64 reference of the Linear Road operator.

Per road segment of a tumbling window: the number of position reports,
their speed sum and mean, the number of stopped vehicles (speed at most
1e-3), an accident where two or more stopped, and the toll: nothing on
an accident segment, else 2 * max(count - 50, 0)**2 * 1e-4. These are
the semantics of ``repro.core.operators.make_lrb`` after the Linear Road
benchmark (Arasu et al., VLDB 2004), written from that description and
not from the program's code; it imports nothing of the program.

``dtype`` selects the arithmetic and ``values`` the type the speeds are
rounded to first: float64 throughout is the reference. The controls run
the same code in bfloat16, or in float32 over speeds stored in bfloat16
(counts stay exact there, so only the speed check can catch it).
"""
from __future__ import annotations

import numpy as np

#: the value lanes the operator reads (lane 0 is the speed)
COLUMNS = 1

STOPPED_SPEED = 1e-3
ACCIDENT_STOPPED = 2
TOLL_BASE = 2.0
TOLL_FREE_COUNT = 50
TOLL_SCALE = 1e-4

#: the numbers compared, each with its limit (PERF.md gives the readings
#: they were set from)
LIMITS = {
    # segments whose count, accident flag or toll differ: counts are
    # exact on any correct fold, and the toll is a function of the count
    "segment_mismatches": 0,
    # float32 speed sums on the device against float64: sound runs read
    # at most 4.1e-6; float32 sums over speeds stored in bfloat16, which
    # keep every count exact, read at least 2.6e-4 (bfloat16 throughout:
    # 1.4); the limit sits past the middle of the two in log terms,
    # with more room above the program's reading than below the control's
    "speed_rel_err": 5e-5,
}
#: the toll is worked out from the count in float32 by the engine
TOLL_RTOL = 1e-6


class Window:
    """Running aggregates of one window."""

    def __init__(self, num_keys: int, dtype=np.float64, values=None):
        self.num_keys = num_keys
        self.dtype = dtype
        self.values = values
        # counts in the arithmetic's own type too: the engine counts in
        # float32, and a bfloat16 count stops at 256
        ct = np.int64 if dtype == np.float64 else dtype
        self.count = np.zeros(num_keys, ct)
        self.speed_sum = np.zeros(num_keys, dtype)
        self.stopped = np.zeros(num_keys, ct)

    def add(self, keys: np.ndarray, vals: np.ndarray) -> None:
        seg = np.asarray(keys) % self.num_keys
        speed = np.asarray(vals[:, 0])
        if self.values is not None:
            speed = speed.astype(self.values)
        speed = speed.astype(self.dtype)
        stopped = seg[speed <= STOPPED_SPEED]
        if self.dtype == np.float64:
            self.count += np.bincount(seg, minlength=self.num_keys)
            self.speed_sum += np.bincount(seg, weights=speed,
                                          minlength=self.num_keys)
            self.stopped += np.bincount(stopped, minlength=self.num_keys)
        else:
            np.add.at(self.count, seg, np.ones(len(seg), self.dtype))
            np.add.at(self.speed_sum, seg, speed)
            np.add.at(self.stopped, stopped,
                      np.ones(len(stopped), self.dtype))

    def result(self) -> dict:
        count = self.count.astype(np.float64)
        avg = (self.speed_sum / np.maximum(self.count, 1).astype(
            self.dtype)).astype(np.float64)
        accident = self.stopped.astype(np.float64) >= ACCIDENT_STOPPED
        congestion = np.maximum(count - TOLL_FREE_COUNT, 0.0)
        toll = np.where(accident, 0.0,
                        TOLL_BASE * congestion ** 2 * TOLL_SCALE)
        return {"count": count, "avg_speed": avg,
                "stopped": self.stopped.astype(np.float64),
                "accident": accident, "toll": toll}


def compare(got: dict, want: dict) -> dict:
    """The numbers of one emission, by name (see ``LIMITS``). The engine
    reports no stopped count: stopped vehicles show through the accident
    flag and the toll."""
    count = np.asarray(got["count"], np.float64)
    toll = np.asarray(got["toll"], np.float64)
    bad = (count != want["count"]) \
        | (np.asarray(got["accident"], bool) != want["accident"]) \
        | (np.abs(toll - want["toll"])
           > TOLL_RTOL * np.maximum(np.abs(want["toll"]), 1.0))
    g = np.asarray(got["avg_speed"], np.float64)
    err = np.abs(g - want["avg_speed"]) \
        / np.maximum(np.abs(want["avg_speed"]), 1e-30)
    return {"segment_mismatches": int(np.sum(bad)),
            "speed_rel_err": float(np.max(err, initial=0.0))}


def oracle(keys, ts, vals, window: float, num_keys: int) -> dict:
    """Whole-stream form: {window start: result} of every tumbling window
    present in ``ts``."""
    wstart = np.floor(ts / window) * window
    out = {}
    for s in np.unique(wstart):
        sel = wstart == s
        w = Window(num_keys)
        w.add(keys[sel], vals[sel])
        out[float(s)] = w.result()
    return out
