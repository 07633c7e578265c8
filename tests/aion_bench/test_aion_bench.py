"""Tests of the on-chip benchmark (``benchmarks/aion/``), off the chip.

The harness runs here at a tiny size on the CPU with its look for a TPU
skipped: its recorded answers must match the plain references, and each
fault planted under the timed path, and each control in the program's
place, must turn ``correct`` false.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

CHECKOUT = Path(__file__).resolve().parents[2]
BENCH = CHECKOUT / "benchmarks" / "aion"
sys.path.insert(0, str(CHECKOUT / "src"))
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import run  # noqa: E402
import readings  # noqa: E402
import trace_reduce  # noqa: E402
from gen import Generator  # noqa: E402

SEED = 2_200_000_123        # above 2**31: seeds need more than 32 bits


def tiny(cell_name: str, traffic: str = None) -> harness.Cell:
    """A cell of the benchmark, cut to a CPU's size: narrow payloads, a
    12-slot arena, 4 s windows, 300 events a step."""
    cell = harness.load_cell(cell_name)
    cfg = dict(cell.config)
    keys = "num_keys" if cfg["operator"] == "stock" else "num_segments"
    cfg.update(value_width=8, block_size=64, pool_slots=12, event_rate=300,
               window_s=4, history_events=6000, arena_bytes=None,
               device_budget_bytes=96 << 10, host_budget_bytes=24 << 10,
               splitk_chunk_rows=4, num_keys=16, operator_args={keys: 16})
    tr = cell.traffic if traffic is None else json.loads(
        (BENCH / "traffic" / f"{traffic}.json").read_text())
    return harness.Cell(cell.name, cfg, tr, cell.end_to_end,
                        cell.per_layer, cell.reference)


def run_tiny(cell, tmp_path, seconds=2.0, trace=False, **kw):
    import jax
    return run.run(cell, SEED, seconds, trace, devices=jax.devices(),
                   t_start=0.0, store_dir=tmp_path / "store", **kw)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "stock.lnorm.max", "--seed", str(SEED), "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300, cwd=CHECKOUT)
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


@pytest.mark.parametrize("cell_name", ["stock.lnorm.max", "lrb.lnorm.max"])
def test_answers_match_the_reference(cell_name, tmp_path):
    out = run_tiny(tiny(cell_name), tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 10 and out["failed"] == 0
    assert set(out["metrics"]) == {m["name"] for m in
                                   tiny(cell_name).end_to_end}
    assert list(out)[-1] == "checks"


def test_open_loop_and_ontime_cells_run(tmp_path):
    out = run_tiny(tiny("stock.lnorm.fixed"), tmp_path)
    assert out["correct"]
    assert "late_staleness_p95_s" in out["metrics"]
    # traffic that no cell runs yet (PERF.md, Open questions)
    out = run_tiny(tiny("stock.lnorm.max", "ontime.max"), tmp_path)
    assert out["correct"]
    # the traced run: the CPU's trace has no device plane to reduce
    out = run_tiny(tiny("stock.lnorm.max", "ontime.max"), tmp_path,
                   trace=True)
    assert out["correct"] and "breakdown" not in out
    assert "engine.ingest_share" in out["metrics"]
    assert "device.idle_share" not in out["metrics"]


def _broken(kind: str):
    """A ``make_engine`` whose engine has one fault planted under the
    calls the window drives."""
    def make(cfg, store_dir, annotate):
        eng = harness.make_engine(cfg, store_dir, annotate)
        op = eng.operator
        if kind == "state_unchanged":
            eng.ingest = lambda batch, now: 0
        elif kind == "half_batch":
            ingest = eng.ingest
            eng.ingest = lambda batch, now: ingest(
                batch.select(np.arange(0, len(batch), 2)), now)
        elif kind == "answer_altered":
            field = "max" if op.name == "stock" else "count"

            def alter(res):
                res = dict(res)
                res[field] = np.array(res[field], copy=True)
                res[field][0] += 1
                return res
            fin, fin_b = op.finalize, op.finalize_batch
            op.finalize = lambda acc: alter(fin(acc))
            op.finalize_batch = lambda acc, n: [alter(r)
                                                for r in fin_b(acc, n)]
        return eng
    return make


@pytest.mark.parametrize("kind", ["state_unchanged", "half_batch",
                                  "answer_altered"])
@pytest.mark.parametrize("cell_name", ["stock.lnorm.max", "lrb.lnorm.max"])
def test_a_broken_timed_path_is_not_correct(cell_name, kind, tmp_path):
    out = run_tiny(tiny(cell_name), tmp_path, make_engine=_broken(kind))
    assert not out["correct"]
    assert out["failed"] > 0


@pytest.mark.parametrize("control", sorted(readings.CONTROLS))
@pytest.mark.parametrize("cell_name", ["stock.lnorm.max", "lrb.lnorm.max"])
def test_the_bfloat16_control_is_not_correct(cell_name, control, tmp_path):
    cell = tiny(cell_name)
    seen = {}

    def on_record(steps, rec, watermark):
        seen["program"] = harness.check(
            steps, cell.reference, cell.config["window_s"],
            cell.config["num_keys"], watermark)
        seen["control"] = harness.check(
            steps, cell.reference, cell.config["window_s"],
            cell.config["num_keys"], watermark,
            control=readings.CONTROLS[control])

    run_tiny(cell, tmp_path, on_record=on_record)
    assert seen["program"]["failed"] == 0
    assert seen["control"]["failed"] > 0
    limits = cell.reference.LIMITS
    over = [k for k, v in seen["control"]["numbers"].items()
            if v > limits[k]]
    assert over, seen["control"]["numbers"]


def test_the_trace_covers_a_slice_that_holds_a_batched_round(tmp_path):
    cell = tiny("stock.lnorm.max")
    eng = harness.make_engine(cell.config, tmp_path / "store", False)
    drv = harness.Driver(eng, 4.0, 1)
    try:
        tracer = run.Tracer(eng)
        seconds = 100.0
        tracer.tick(drv, 0.0, seconds)
        assert tracer.session is None and not drv.annotate
        start = run.TRACE_FROM * seconds
        tracer.tick(drv, start, seconds)
        assert tracer.session is not None and drv.annotate
        # past the least length, but no batched round yet: on
        tracer.tick(drv, start + run.TRACE_MIN_S + 1, seconds)
        assert tracer.session is not None
        eng.metrics.batch_executions += 1
        tracer.tick(drv, start + run.TRACE_MIN_S + 2, seconds)
        assert tracer.session is None and not drv.annotate
        tracer.tick(drv, start + run.TRACE_MIN_S + 3, seconds)
        assert tracer.session is None and not drv.annotate
        got = tracer.finish(drv)
        assert got["data"] is not None
        assert got["counters"]["batch_executions"] == 1
        # a window too short for the slice to begin has no trace
        assert run.Tracer(eng).finish(drv) is None
    finally:
        eng.close()


def test_trace_reduction_of_the_recorded_trace():
    """``fixtures/fixture.xplane.pb``: two bench.step spans on a v5e, each
    folding 256 arena rows through the stock split-K fold and the Linear
    Road take-then-flat fold, then 50 ms of host sleep
    (``record_fixture_trace.py``)."""
    r = trace_reduce.reduce(BENCH / "fixtures" / "fixture.xplane.pb",
                            harness.FOLD_KERNELS)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.10747895, rel=1e-9)
    assert r["busy_s"] == pytest.approx(0.002731352, rel=1e-9)
    assert r["kernel_s"] == pytest.approx(0.001281447, rel=1e-9)
    ops = dict(r["device_ops"])
    assert ops["segment_aggregate_batched"] == pytest.approx(0.000933259)
    assert ops["segment_aggregate_block_table_splitk"] == \
        pytest.approx(0.000348188)
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.ingest"] == pytest.approx(0.104468219)
    idle = sum(gaps.values())
    assert idle == pytest.approx(r["window_s"] - r["busy_s"], rel=1e-9)


def test_metric_readers_on_a_traced_record():
    rec = {"setup_s": 50.0, "peak_bytes": 3 << 30,
           "device_kind": "TPU v5 lite",
           "config": harness.load_cell("stock.lnorm.max").config,
           "window": {"seconds": 30.0, "steps": 60, "events": 600_000},
           "host": {"ingest_s": 6.0, "control_s": 21.0, "round_s": 18.0},
           "counters": {"exec_seconds": 18.0, "pooled_rows": 900,
                        "fallback_rows": 100, "demand_pool_fills": 60,
                        "store_bytes_read": 1_200_000_000,
                        "batched_windows": 10},
           "compiles": {"lowered": 2},
           "gen_lag_s": [0.0, 0.01, 0.02, 0.4],
           "staleness": {"ages": np.array([1.0, 2.0, 30.0]),
                         "weights": np.array([10, 80, 10])},
           "trace": {"window_s": 30.0, "busy_s": 0.6, "kernel_s": 0.005,
                     "counters": {"pooled_rows": 900, "fallback_rows": 100,
                                  "batched_windows": 10}}}
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    read = {m["name"]: harness.reader(m["name"])(rec)
            for m in bench["end_to_end"] + bench["per_layer"]}
    assert read["events_per_s"] == 20_000
    assert read["device.memory_peak_gib"] == 3.0
    assert read["trigger.late_staleness_p50_s.fixed"] == 2.0
    assert read["late_staleness_p95_s"] == 30.0
    assert read["engine.ingest_share"] == pytest.approx(20.0)
    assert read["engine.control_self_share"] == pytest.approx(10.0)
    assert read["pool.fallback_row_share"] == pytest.approx(10.0)
    assert read["device.idle_share"] == pytest.approx(98.0)
    # 1,000 rows x 512 events x (4 B key + 4 B price) + 10 windows x 128
    # symbols x 4 accumulators x 4 B, at 819 GB/s, over 5 ms
    want = 100 * (1000 * 512 * 8 + 10 * 128 * 16) / 819e9 / 0.005
    assert read["kernel.fold_roofline_share"] == pytest.approx(want)
    assert read["kernel.fold_roofline_share.fixed"] == pytest.approx(want)
    assert 0 < read["kernel.fold_roofline_share"] < 100
    rec["device_kind"] = "TPU v9"
    with pytest.raises(KeyError):
        harness.reader("kernel.fold_roofline_share")(rec)


def test_every_cell_finds_its_files():
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = harness.load_cell(w["name"])
        assert cell.per_layer, w["name"]
        for m in cell.end_to_end + cell.per_layer:
            assert callable(harness.reader(m["name"]))
        for c in bench["configs"]:
            if c["name"] == w["config"]:
                assert (CHECKOUT / c["file"]).exists()


def test_linear_road_reference_on_a_hand_computed_window():
    lrb = harness.load_module(BENCH / "reference" / "lrb.py")
    # segment 0: two cars at 50 and 70; segment 1 (keys 1 and 257): two
    # stopped cars and two moving; segment 2: 60 cars at 10, over the
    # 50 that ride free; one report in the next window
    keys = np.array([0, 0, 1, 1, 1, 257] + [2] * 60 + [0])
    speed = np.array([50, 70, 0, 0, 30, 40] + [10] * 60 + [99.0])
    ts = np.array([1.0] * 66 + [61.0])
    out = lrb.oracle(keys, ts, speed[:, None], 60.0, 256)
    w = out[0.0]
    assert w["count"][:3].tolist() == [2, 4, 60]
    assert w["avg_speed"][:3].tolist() == [60.0, 17.5, 10.0]
    assert w["accident"][:3].tolist() == [False, True, False]
    assert w["toll"][:3].tolist() == [0.0, 0.0, 2 * 10 ** 2 * 1e-4]
    assert w["count"][3:].sum() == 0
    assert out[60.0]["count"][0] == 1


def test_generator_is_fixed_by_its_seed():
    cell = harness.load_cell("stock.lnorm.max")
    a = Generator(cell.config, cell.traffic, SEED).batch(1000, 300.0)
    b = Generator(cell.config, cell.traffic, SEED).batch(1000, 300.0)
    c = Generator(cell.config, cell.traffic, SEED + 1).batch(1000, 300.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[1], c[1])
    keys, ts, vals = a
    assert vals.shape == (1000, 416) and vals.dtype == np.float32
    # Table 1: windowIndex = floor(LogNormal(0, 1)); half of the events
    # fall in windows that closed already
    widx = np.floor((300.0 - ts) / 30.0)
    assert 0.4 < np.mean(widx >= 1) < 0.6
    ontime = json.loads((BENCH / "traffic" / "ontime.max.json").read_text())
    _, ts, _ = Generator(cell.config, ontime, SEED).batch(1000, 300.0)
    assert ts.min() >= 299.0 and ts.max() < 300.0
