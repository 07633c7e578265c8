#!/usr/bin/env python3
"""One run of a cell, as ``run.py`` makes it, that also prints the
controls' readings: the numbers that decide ``correct`` when the plain
reference takes the program's place on the same answers, computed

- ``bfloat16``: in bfloat16 throughout, the precision below the float32
  that the configuration states;
- ``bfloat16_values``: in float32 over value lanes stored in bfloat16,
  the lower-precision storage a later change might try.

The limits in ``reference/<operator>.py`` lie between the program's
readings (the run's ``checks``) and the controls'.

    python3 benchmarks/aion/readings.py --workload <workload> \\
        --seed 7 --seconds 51 --trace 0

Prints the run's result line and, last on standard error before the
checks, ``control <name>:`` and that control's numbers as JSON. The
benchmark's own runs do not run the controls.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402

import ml_dtypes  # noqa: E402
import numpy as np  # noqa: E402

import run  # noqa: E402
import harness  # noqa: E402

#: keyword arguments of the reference's ``Window``, by control
CONTROLS = {
    "bfloat16": {"dtype": ml_dtypes.bfloat16},
    "bfloat16_values": {"dtype": np.float32, "values": ml_dtypes.bfloat16},
}


def control(cell: harness.Cell):
    """An ``on_record`` for ``run.run`` that checks the run's answers
    with each control and prints its numbers."""
    def on_record(steps, rec, watermark):
        for name, kw in CONTROLS.items():
            got = harness.check(steps, cell.reference,
                                harness.Windows.of(cell.config),
                                cell.config["num_keys"], watermark,
                                control=kw)
            print(f"control {name}: " + json.dumps(got["numbers"]),
                  file=sys.stderr, flush=True)
    return on_record


def main() -> int:
    args = run.parse(sys.argv[1:])
    cell = harness.load_cell(args.workload)
    devices = run.chips(args.workload)
    if devices is None:
        return 2
    run.enable_compile_cache(run.CACHE_DIR)
    out = run.run(cell, args.seed, args.seconds, bool(args.trace),
                  devices=devices, t_start=T_START,
                  on_record=control(cell))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
