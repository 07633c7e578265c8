"""``chip_smoke.py`` off the chip: it refuses to run without a TPU, its
oracle check catches a wrong result, and its stream driver holds the
engine to the oracle at a small size on the CPU."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

from repro.core.events import EventBatch  # noqa: E402
from repro.core.windows import WindowId  # noqa: E402


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                         capture_output=True, text=True, env=env,
                         timeout=120, cwd=ROOT)
    assert out.returncode == 2
    assert "no TPU found" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_oracle_catches_a_wrong_result():
    oracle = chip_smoke.Oracle(window=30.0, num_keys=4)
    batch = EventBatch(np.array([0, 1, 1, 3]), np.array([1.0, 2.0, 3.0, 40.0]),
                       np.array([[10.0], [20.0], [22.0], [5.0]], np.float32))
    oracle.add(batch)
    wid = WindowId(0.0, 30.0)
    want = oracle.want(wid)
    np.testing.assert_array_equal(want["mean"], [10.0, 21.0, 0.0, 0.0])
    good = {k: np.array(v) for k, v in want.items()}
    oracle.compare(wid, good, want)
    bad = dict(good, min=np.array([10.0, 22.0, np.inf, np.inf]))
    with pytest.raises(AssertionError):
        oracle.compare(wid, bad, want)


def test_peak_over_its_bound_fails():
    plan = chip_smoke.Plan()
    chip_smoke.check_peak(plan, [plan.peak_hbm_bound, 0])
    for peaks in ([plan.peak_hbm_bound + 1], [0, None]):
        with pytest.raises(AssertionError):
            chip_smoke.check_peak(plan, peaks)


def test_stream_matches_oracle_at_small_size(tmp_path):
    """The smoke's driver at a CPU size: every emission of the engine
    (dense folds here) matches the oracle, late waves re-execute, and
    the tiny arena overflows into the fallback and the store."""
    plan = chip_smoke.Plan(windows=2, rate=150, late_waves=1,
                           late_wave_events=600, pool_slots=12,
                           device_budget=24 << 20, host_budget=2 << 20,
                           splitk_chunk_rows=4, warmup_windows=1)
    oracle = chip_smoke.Oracle(30.0, 128)
    compiles = chip_smoke.CompileLog()
    eng = chip_smoke.make_engine(plan, tmp_path)
    try:
        ran = chip_smoke.drive(plan, 0, [eng], oracle, compiles,
                               t0=time.time())
        assert ran["windows"] == 2
        assert eng.metrics.late_executions > 0
        assert eng.io.stats["spilled_blocks"] > 0
    finally:
        eng.close()
    assert oracle.checked >= 2
    assert oracle.worst_extrema == 0.0
