"""The Pallas folds' share of the chip's memory roofline, in %.

The work is defined by the fold, not by how a kernel does it, so the
same work counts the same whatever implements it. Each row folded in a
batched round (pooled or fallback, over the traced slice) is a
block of ``block_size`` events, and each event needs its key (4 B) and
the value lanes the operator reads (4 B each); each window of a round
writes its accumulators once (4 B per segment and statistic):

    bytes = rows * block_size * (4 + 4 * columns)
            + windows * num_keys * accumulators * 4

The fold reads each byte once and does a few operations per byte, so
memory bounds it: least time = bytes / peak bandwidth of the chip
(``peaks.json``, by device kind). The share is that least time over the
fold kernels' summed device time in the trace.
"""

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parents[1] / "peaks.json"


def needed_bytes(rows: int, windows: int, config: dict) -> int:
    work = config["fold_work"]
    return (rows * config["block_size"] * (4 + 4 * work["columns"])
            + windows * config["num_keys"] * work["accumulators"] * 4)


def read(rec):
    t = rec["trace"]
    if t is None or t["kernel_s"] <= 0:
        return None
    c = t["counters"]
    rows = c["pooled_rows"] + c["fallback_rows"]
    if rows == 0:
        return None
    peaks = json.loads(PEAKS.read_text())
    kind = rec["device_kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in {PEAKS}")
    least = needed_bytes(rows, c["batched_windows"], rec["config"]) \
        / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least / t["kernel_s"]
