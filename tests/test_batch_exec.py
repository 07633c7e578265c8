"""Batched multi-window execution: parity vs the per-window reference.

The batched path (core/batch_exec.py) must produce results equal — up to
float associativity — to the per-window reference path, for every
operator that implements the batch contract, under a late-heavy scenario
where one poll batches live expiries AND late re-executions of many
windows at once.
"""
import numpy as np
import pytest

from repro.configs.base import AionConfig
from repro.core import StreamEngine, TumblingWindows
from repro.core.events import EventBatch
from repro.core.operators import make_operator
from repro.core.triggers import DeltaTTrigger

WINDOW = 10.0
N_WINDOWS = 10


def _make_engine(op_name: str, batched: bool, block: int = 64,
                 width: int = 2, num_keys: int = 8,
                 pooled: bool = True) -> StreamEngine:
    aion = AionConfig(block_size=block, batched_execution=batched,
                      block_pool=pooled)
    kw = {}
    if op_name == "stock":
        kw = {"num_keys": num_keys}
    elif op_name == "lrb":
        kw = {"num_segments": num_keys}
    elif op_name == "bigrams":
        kw = {"vocab": 16}
    op = make_operator(op_name, block, width, **kw)
    return StreamEngine(
        assigner=TumblingWindows(WINDOW), operator=op, aion=aion,
        value_width=width, device_budget_bytes=64 << 20,
        trigger=DeltaTTrigger(executions=2),
    )


def _late_heavy_run(eng: StreamEngine, seed: int = 7):
    """Many concurrent windows expiring together, then a late wave into
    most of them — the batch path sees mixed-occupancy live and late
    batches."""
    rng = np.random.default_rng(seed)
    horizon = N_WINDOWS * WINDOW
    n = 3000
    b = EventBatch(rng.integers(0, 8, n),
                   rng.uniform(0, horizon, n),
                   rng.normal(size=(n, 2)).astype(np.float32))
    eng.ingest(b, now=0.0)
    eng.advance_watermark(horizon, now=horizon)      # all windows expire
    nl = 900
    late = EventBatch(rng.integers(0, 8, nl),
                      rng.uniform(0, horizon - WINDOW, nl),
                      rng.normal(size=(nl, 2)).astype(np.float32))
    eng.ingest(late, now=horizon + 1.0)
    for t in np.linspace(horizon + 1,
                         horizon + 1 + 2 * eng.cleanup.current_bound(), 25):
        eng.poll(t)
    results = dict(eng.results)
    metrics = eng.metrics
    eng.close()
    return results, metrics


def _assert_equal_results(got, want, op_name):
    assert set(got) == set(want)
    for wid in want:
        g, w = got[wid], want[wid]
        if isinstance(w, dict):
            for k in w:
                np.testing.assert_allclose(
                    np.asarray(g[k], np.float64),
                    np.asarray(w[k], np.float64), rtol=1e-4, atol=1e-5,
                    err_msg=f"{op_name} {wid} field {k!r}")
        else:
            assert g == pytest.approx(w, rel=1e-4, abs=1e-5), \
                f"{op_name} {wid}"


@pytest.mark.parametrize("pooled", [True, False])
@pytest.mark.parametrize("op_name", ["average", "stock", "lrb", "bigrams"])
def test_batched_matches_reference_late_heavy(op_name, pooled):
    got, m_b = _late_heavy_run(_make_engine(op_name, batched=True,
                                            pooled=pooled))
    want, m_r = _late_heavy_run(_make_engine(op_name, batched=False,
                                             pooled=pooled))
    _assert_equal_results(got, want, op_name)
    # the batched run actually used the batch path, and with real occupancy
    assert m_b.batch_executions >= 1
    assert m_b.mean_batch_occupancy > 1.0
    assert m_b.batched_windows >= N_WINDOWS
    assert m_b.batch_dispatch_seconds > 0.0
    if pooled:
        # zero-copy block-table rows carried the batch
        assert m_b.pooled_rows > 0
    else:
        assert m_b.pooled_rows == 0
    # the reference run never did
    assert m_r.batch_executions == 0
    # both executed every window live, and re-executed late ones
    assert m_b.live_executions == m_r.live_executions == N_WINDOWS
    assert m_b.late_executions >= 1 and m_r.late_executions >= 1


def test_live_batch_occupancy_counts_all_due_windows():
    """>= 8 concurrent due windows fold in ONE device pass."""
    eng = _make_engine("average", batched=True)
    rng = np.random.default_rng(3)
    n = 2000
    b = EventBatch(rng.integers(0, 8, n),
                   rng.uniform(0, N_WINDOWS * WINDOW, n),
                   rng.normal(size=(n, 2)).astype(np.float32))
    eng.ingest(b, now=0.0)
    eng.advance_watermark(N_WINDOWS * WINDOW, now=N_WINDOWS * WINDOW)
    assert eng.metrics.batch_executions == 1
    assert eng.metrics.batch_occupancy_series == [N_WINDOWS]
    assert eng.metrics.live_executions == N_WINDOWS
    eng.close()


def test_percentile_batched_matches_quantile_oracle():
    """The blocking percentile operator now carries a real batch
    contract (sorted-run accumulators); the batched path must produce
    the same quantiles np.quantile computes from the raw events."""
    aion = AionConfig(block_size=64, batched_execution=True)
    op = make_operator("percentile", 64, 1)
    assert op.supports_batch          # fold_batch landed with split-K
    assert op.supports_splitk
    eng = StreamEngine(
        assigner=TumblingWindows(WINDOW), operator=op, aion=aion,
        value_width=1, device_budget_bytes=64 << 20,
        trigger=DeltaTTrigger(executions=1),
    )
    rng = np.random.default_rng(5)
    n = 1200
    b = EventBatch(np.zeros(n, np.int32), rng.uniform(0, 30.0, n),
                   rng.uniform(0, 1, (n, 1)).astype(np.float32))
    eng.ingest(b, now=0.0)
    eng.advance_watermark(30.0, now=30.0)
    assert eng.metrics.batch_executions >= 1
    from repro.core.windows import WindowId
    ts = b.timestamps
    for s in (0.0, 10.0, 20.0):
        sel = (ts >= s) & (ts < s + 10.0)
        res = eng.results[WindowId(s, s + 10.0)]
        for q in (0.5, 0.95, 0.99):
            want = float(np.quantile(b.values[sel, 0], q))
            assert res[q] == pytest.approx(want, rel=1e-4, abs=1e-5)
    eng.close()


def test_single_due_window_uses_reference_path():
    """A batch of one gains nothing from stacking; the executor routes it
    through execute_window."""
    eng = _make_engine("average", batched=True)
    rng = np.random.default_rng(9)
    b = EventBatch(rng.integers(0, 8, 300), rng.uniform(0, 10.0, 300),
                   rng.normal(size=(300, 2)).astype(np.float32))
    eng.ingest(b, now=0.0)
    eng.advance_watermark(10.0, now=10.0)
    assert eng.metrics.live_executions == 1
    assert eng.metrics.batch_executions == 0
    from repro.core.windows import WindowId
    assert eng.results[WindowId(0.0, 10.0)] == pytest.approx(
        float(np.mean(b.values[:, 0])), rel=1e-4, abs=1e-5)
    eng.close()


def test_batched_respects_priority_rule_live_before_late():
    """Within one watermark+poll cycle, the live batch's executions land
    before the late batch's (paper §3: live work outranks re-execution)."""
    eng = _make_engine("average", batched=True)
    rng = np.random.default_rng(11)
    horizon = N_WINDOWS * WINDOW
    b = EventBatch(rng.integers(0, 8, 1500), rng.uniform(0, horizon, 1500),
                   rng.normal(size=(1500, 2)).astype(np.float32))
    eng.ingest(b, now=0.0)
    eng.advance_watermark(horizon, now=horizon)
    live_first = eng.metrics.live_executions
    assert eng.metrics.late_executions == 0   # nothing late yet
    late = EventBatch(rng.integers(0, 8, 400),
                      rng.uniform(0, horizon - WINDOW, 400),
                      rng.normal(size=(400, 2)).astype(np.float32))
    eng.ingest(late, now=horizon + 1.0)
    for t in np.linspace(horizon + 1,
                         horizon + 1 + 2 * eng.cleanup.current_bound(), 20):
        eng.poll(t)
    assert eng.metrics.live_executions == live_first   # no new live work
    assert eng.metrics.late_executions >= 1
    eng.close()


# ------------------------------------------------------------ split-K path

def _make_splitk_engine(op_name: str, chunk: int, **kw) -> StreamEngine:
    import dataclasses
    eng = _make_engine(op_name, batched=True, **kw)
    eng.aion = dataclasses.replace(eng.aion, splitk_chunk_rows=chunk)
    return eng


@pytest.mark.parametrize("op_name",
                         ["average", "stock", "lrb", "percentile"])
def test_splitk_engine_parity(op_name):
    """splitk_chunk_rows > 0 changes only the fold decomposition: engine
    results match the unchunked batched run for every split-K operator,
    and the chunked path actually launched."""
    want, m0 = _late_heavy_run(_make_engine(op_name, batched=True))
    got, m1 = _late_heavy_run(_make_splitk_engine(op_name, chunk=2))
    _assert_equal_results(got, want, op_name)
    assert m1.splitk_launches > 0
    assert m0.splitk_launches == 0


def test_splitk_auto_disables_below_one_chunk():
    """Rounds smaller than one chunk per device fall back to the stripe
    fold — no split-K launches, identical results."""
    want, _ = _late_heavy_run(_make_engine("average", batched=True))
    got, m = _late_heavy_run(_make_splitk_engine("average", chunk=4096))
    _assert_equal_results(got, want, "average")
    assert m.splitk_launches == 0


def test_splitk_ignored_for_unsupported_operator():
    """bigrams' slot-ownership scatter cannot take balanced/chunked rows;
    the knob must be a no-op for it (supports_splitk=False)."""
    want, _ = _late_heavy_run(_make_engine("bigrams", batched=True))
    got, m = _late_heavy_run(_make_splitk_engine("bigrams", chunk=2))
    _assert_equal_results(got, want, "bigrams")
    assert m.splitk_launches == 0


def test_splitk_launch_shapes_closed_under_batch_size():
    """The zero-recompile property: whatever the pooled row count, the
    planner only ever emits launch groups of {1,2,4,8} x chunk rows, so
    a handful of warmed shapes serves every round."""
    eng = _make_splitk_engine("average", chunk=4)
    planner = eng.batch_exec

    class _Blk:
        fill = 3

    shapes = set()
    for rows in (5, 7, 16, 33, 100, 257, 1023):
        # (block, window_slot, pool_slot) rows; only the count matters
        fake = [(_Blk(), i % 7, i) for i in range(rows)]
        groups = planner._plan_table_groups(fake, num_devices=1,
                                            slots_per=7)
        for table, fills, slots, sk in groups:
            assert sk == 4
            assert table.shape == fills.shape == slots.shape
            shapes.add(int(table.shape[0]))
    assert shapes <= {4, 8, 16, 32}          # {1,2,4,8} groups x chunk 4
    eng.close()


def test_splitk_zero_recompiles_across_late_waves():
    """Across late waves of varying size the fold cache stops growing
    once the pow2 group shapes are warm."""
    eng = _make_splitk_engine("average", chunk=2)
    rng = np.random.default_rng(13)
    horizon = N_WINDOWS * WINDOW
    b = EventBatch(rng.integers(0, 8, 3000),
                   rng.uniform(0, horizon, 3000),
                   rng.normal(size=(3000, 2)).astype(np.float32))
    eng.ingest(b, now=0.0)
    eng.advance_watermark(horizon, now=horizon)
    now = horizon
    sizes = (900, 333, 57, 1500, 64, 711)
    cache_after = []
    for nl in sizes:
        late = EventBatch(rng.integers(0, 8, nl),
                          rng.uniform(0, horizon - WINDOW, nl),
                          rng.normal(size=(nl, 2)).astype(np.float32))
        now += 1.0
        eng.ingest(late, now=now)
        for t in np.linspace(now, now + 2 * eng.cleanup.current_bound(),
                             10):
            eng.poll(t)
        now = t
        cache_after.append(eng.operator.fold_batch._cache_size())
    assert eng.metrics.splitk_launches > 0
    # the tail waves (every group shape warm) compile nothing new
    assert cache_after[-1] == cache_after[1], cache_after
    eng.close()


def test_splitk_all_rows_demoted_mid_round():
    """A round whose every pooled row demotes to the stacked fallback
    (no pool at all: classify finds zero resident rows) must still
    finish: zero chunk groups, correct results from fallback alone."""
    eng = _make_splitk_engine("average", chunk=2, pooled=False)
    got, m = _late_heavy_run(eng)
    want, _ = _late_heavy_run(_make_engine("average", batched=True,
                                           pooled=False))
    _assert_equal_results(got, want, "average")
    assert m.splitk_launches == 0          # nothing pooled to chunk
    assert m.batch_executions >= 1
