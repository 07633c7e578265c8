"""Differential soak test: the full engine vs a never-spilling oracle.

Drives ``StreamEngine`` through ~50k synthetic events with heavy
lateness, random watermark advances, a mid-stream checkpoint/restore, and
sustained spill pressure (tiny device + host budgets with a spill dir),
then asserts that every window's final result matches a trivially-correct
in-memory oracle — a plain numpy group-by over ALL events ever generated.
Runs the (batched x slot-sharded x block-pool) config matrix; slot
sharding actually shards under ``make verify-multidevice`` (8 simulated
CPU devices) and is a checked no-op on the single-device tier-1
container; ``block_pool`` routes the batched gather through the
persistent device arena (block tables + demand pool-fills) under the
same spill pressure and mid-stream restore.

Railgun-style rationale (PAPERS.md): partitioned streaming state is only
trustworthy while it is continuously validated against an oracle — the
soak is that validation for the tiered-state + batched + sharded stack.
"""
import contextlib

import numpy as np
import pytest
import jax

from repro.configs.base import AionConfig
from repro.core import StreamEngine, TumblingWindows
from repro.core.batch_exec import BatchWorkItem
from repro.core.cleanup import PredictiveCleanup
from repro.core.events import EventBatch
from repro.core.operators import make_operator
from repro.core.triggers import DeltaTTrigger
from repro.core.windows import WindowId
from repro.distributed.fault import EngineRecovery
from repro.testing import (
    FaultInjector, FaultyBlockStore, oracle_average, oracle_stock,
)

#: store ops the chaos axis injects on. Deliberately NOT ``delete``:
#: purges/reconciles run on the engine main thread outside the retry
#: envelope, and the chaos contract is about the data path.
_CHAOS_OPS = ("get", "put", "commit", "readahead")

WINDOW = 10.0
N_EVENTS = 50_000
CHUNK = 1_000
MAX_LATE = 25.0           # heavy lateness: up to 2.5 windows
SEED = 1234


class _NoPurgeCleanup(PredictiveCleanup):
    """Purge-free cleanup for the differential harness.

    The oracle accounts every event forever; purging engine state and
    then receiving more late events for that window would (correctly, per
    the paper's coverage contract) diverge from the oracle, so the soak
    pins a moderate re-execution horizon and disables purging. Purge
    behaviour is covered by the engine unit tests.
    """

    def should_purge(self, window_end: float, watermark: float) -> bool:
        return False


def _cleanup() -> _NoPurgeCleanup:
    # fixed 60s horizon (6 windows > MAX_LATE): min_history keeps the
    # DKW estimator from ever replacing it mid-run
    return _NoPurgeCleanup(initial_bound=60.0, min_history=1 << 62)


def _make_engine(op_name: str, batched: bool, sharded: bool,
                 spill_dir, width: int,
                 pooled: bool = False,
                 store: str = "log",
                 pipelined: bool = False,
                 prefetch: str = "fixed",
                 splitk: int = 0,
                 fault_rate: float = 0.0,
                 fault_seed: int = 0,
                 ladder: bool = True) -> StreamEngine:
    extra = {}
    if fault_rate > 0:
        # chaos axis: zero backoff keeps ~50k-event soaks fast, a low
        # breaker threshold makes the ladder engage under the injected
        # error bursts (store traffic is bursty: destage/spill groups,
        # re-execution fetch fans); ladder=False = the ablation control
        extra = dict(io_retry_backoff=0.0,
                     breaker_error_threshold=2 if ladder else 0)
    aion = AionConfig(block_size=256, batched_execution=batched,
                      slot_sharding=sharded, block_pool=pooled,
                      store_backend=store,
                      store_segment_bytes=128 << 10,
                      pipelined_execution=pipelined,
                      prefetch_backend=prefetch,
                      splitk_chunk_rows=splitk, **extra)
    store_obj = None
    if fault_rate > 0:
        from repro.storage import make_store
        inner = make_store("log", spill_dir, segment_bytes=128 << 10)
        inj = FaultInjector(
            seed=fault_seed,
            rates={op: fault_rate for op in _CHAOS_OPS},
            # failure streaks stay below io_retry_limit, so the retry
            # path deterministically recovers: gave_up == 0 is EXACT
            max_consecutive=2)
        store_obj = FaultyBlockStore(inner, inj)
    kw = {"num_keys": 8} if op_name == "stock" else {}
    # spill pressure: ~1 MB device budget (~256 blocks), ~512 KB host
    # budget -> blocks continuously destage AND spill to storage. The
    # chaos axis squeezes both 8x so the run is *dominated* by store
    # traffic -- every fold crosses the faulty get/put/commit path.
    dev_budget = 1 << 17 if fault_rate > 0 else 1 << 20
    host_budget = 1 << 16 if fault_rate > 0 else 1 << 19
    eng = StreamEngine(
        assigner=TumblingWindows(WINDOW),
        operator=make_operator(op_name, aion.block_size, width, **kw),
        aion=aion, value_width=width,
        cleanup=_cleanup(),
        trigger=DeltaTTrigger(executions=2),
        device_budget_bytes=dev_budget,
        host_budget_bytes=host_budget,
        spill_dir=spill_dir,
        store=store_obj,
    )
    if store_obj is not None:
        eng._fault_injector = store_obj.injector
    return eng


def _final_sweep(eng: StreamEngine, now: float) -> None:
    """Re-execute every window through the engine's own (batched or
    reference) path so final results reflect all folded-in late events —
    including plans lost at the mid-stream restore."""
    eng.flush_deferred(now)   # backpressure deferral must never be loss
    if eng.pipeline is not None:
        assert eng.pipeline.drain(), "fold pipeline failed to drain"
    assert eng.io.drain(), "I/O executor failed to drain"
    items = [BatchWorkItem(wid, eng.windows[wid], True)
             for wid in sorted(eng.windows)]
    if eng.batching_enabled and len(items) > 1:
        eng.batch_exec.execute(items, now)
    else:
        for it in items:
            eng.execute_window(it.wid, now, late=True)


_COUNTERS = ("ingested", "ingested_late", "live_executions",
             "late_executions", "batch_executions",
             "sharded_batch_executions", "pooled_rows", "fallback_rows",
             "demand_pool_fills", "pipeline_rounds", "epoch_demoted_rows",
             "splitk_launches",
             # self-healing ladder observables (ISSUE 9)
             "shed_readahead_drives", "shed_prefetch_rounds",
             "demoted_sync_rounds", "deferred_events",
             "readmitted_events")

_IO_COUNTERS = ("errors", "retries", "gave_up", "readahead_shed",
                "staged_blocks")


class _SoakTotals:
    """Counter totals across both engine incarnations (the restore swaps
    in a fresh engine whose metrics start at zero)."""

    def __init__(self):
        for k in _COUNTERS:
            setattr(self, k, 0)
        for k in _IO_COUNTERS:
            setattr(self, "io_" + k, 0)
        self.injected_faults = 0
        self.ladder_transitions = []

    def absorb(self, eng) -> None:
        for k in _COUNTERS:
            setattr(self, k, getattr(self, k) + getattr(eng.metrics, k))
        for k in _IO_COUNTERS:
            setattr(self, "io_" + k,
                    getattr(self, "io_" + k) + eng.io.stats[k])
        self.ladder_transitions.extend(eng.metrics.ladder_transitions)
        inj = getattr(eng, "_fault_injector", None)
        if inj is not None:
            self.injected_faults += inj.stats["injected"]


def _drive(op_name: str, batched: bool, sharded: bool, spill_dir,
           width: int = 1, pooled: bool = False, store: str = "log",
           pipelined: bool = False, prefetch: str = "fixed",
           splitk: int = 0, fault_rate: float = 0.0,
           fault_seed: int = 0, ladder: bool = True):
    """Run the soak; returns (results, oracle_events, counter_totals)."""
    rng = np.random.default_rng(SEED)
    totals = _SoakTotals()
    eng = _make_engine(op_name, batched, sharded, spill_dir / "a", width,
                       pooled, store, pipelined, prefetch, splitk,
                       fault_rate, fault_seed, ladder)
    all_events = []           # oracle ledger: every event ever generated
    now = 0.0
    wm = 0.0
    emitted = 0
    restored = False
    while emitted < N_EVENTS:
        n = min(CHUNK, N_EVENTS - emitted)
        # heavy lateness: 65% fresh, 25% late up to MAX_LATE, 10% very
        # late (uniform over the full late range)
        u = rng.random(n)
        delay = np.where(
            u < 0.65, rng.uniform(0.0, 2.0, n),
            np.where(u < 0.90, rng.uniform(0.0, MAX_LATE, n),
                     rng.uniform(MAX_LATE * 0.6, MAX_LATE, n)))
        ts = np.maximum(now - delay, 0.0)
        batch = EventBatch(rng.integers(0, 8, n), ts,
                           rng.normal(size=(n, width)).astype(np.float32))
        all_events.append((batch.keys.copy(), batch.timestamps.copy(),
                           batch.values.copy()))
        eng.ingest(batch, now)
        emitted += n
        # random watermark advances: sometimes lag, sometimes jump ahead
        if rng.random() < 0.7:
            wm = max(wm, now - rng.uniform(0.0, 5.0))
            eng.advance_watermark(wm, now)
        eng.poll(now)
        now += rng.uniform(1.0, 4.0)            # random processing pace

        if not restored and emitted >= N_EVENTS // 2:
            # mid-stream crash/restore: serialize, rebuild, resume.
            # Under chaos the checkpoint itself runs fault-free (it is
            # the recovery anchor, not the victim).
            restored = True
            inj = getattr(eng, "_fault_injector", None)
            ctx = inj.paused() if inj is not None else \
                contextlib.nullcontext()
            with ctx:
                snap = eng.checkpoint_state()
                totals.absorb(eng)
                eng.close()
            eng = _make_engine(op_name, batched, sharded,
                               spill_dir / "b", width, pooled, store,
                               pipelined, prefetch, splitk,
                               fault_rate, fault_seed + 1, ladder)
            inj_b = getattr(eng, "_fault_injector", None)
            ctx = inj_b.paused() if inj_b is not None else \
                contextlib.nullcontext()
            with ctx:
                eng.restore_state(snap)

    # close out: expire everything, fire remaining re-execution plans,
    # then a final full sweep through the engine's own execution path
    wm = now + MAX_LATE
    eng.advance_watermark(wm, now)
    for t in np.linspace(now, now + 70.0, 8):
        eng.poll(t)
    _final_sweep(eng, now + 70.0)
    results = dict(eng.results)
    totals.absorb(eng)
    eng.close()
    keys = np.concatenate([k for k, _, _ in all_events])
    tss = np.concatenate([t for _, t, _ in all_events])
    vals = np.concatenate([v for _, _, v in all_events])
    return results, (keys, tss, vals), totals


@pytest.mark.parametrize("batched,sharded,pooled,store", [
    # the default persistent tier is the log-structured store
    (True, True, True, "log"), (True, False, True, "log"),  # block table
    (True, True, False, "log"), (True, False, False, "log"),  # stacked
    (False, True, False, "log"), (False, False, False, "log"),
    # legacy npz fallback backend: the same soak over the
    # file-per-block persistent tier (store ablation axis)
    (True, False, True, "npz"), (True, True, False, "npz"),
    # no (batched=False, pooled=True) row: the engine only builds the
    # pool when the batched path can consume block tables, so that
    # config is byte-identical to all-off (pooled per-window folds are
    # covered via single-window batches inside the pooled rows above)
])
def test_soak_differential_average(tmp_path, batched, sharded, pooled,
                                   store):
    results, (keys, ts, vals), totals = _drive(
        "average", batched, sharded, tmp_path, pooled=pooled, store=store)
    want = oracle_average(keys, ts, vals, WINDOW)
    assert set(results) == set(want)
    for wid in want:
        assert results[wid] == pytest.approx(want[wid], rel=2e-4,
                                             abs=2e-4), wid
    # the soak exercised what it claims to exercise
    assert totals.ingested == N_EVENTS
    assert totals.ingested_late > N_EVENTS // 10       # heavy lateness
    assert totals.late_executions > 0
    if batched:
        assert totals.batch_executions > 0
    else:
        assert totals.batch_executions == 0
    if sharded and batched and len(jax.devices()) > 1:
        assert totals.sharded_batch_executions > 0
    else:
        assert totals.sharded_batch_executions == 0
    if pooled and batched:
        # the block-table path really carried rows under spill pressure
        assert totals.pooled_rows > 0
    else:
        assert totals.pooled_rows == 0


@pytest.mark.parametrize("sharded,pooled", [
    (True, True), (False, True), (True, False), (False, False),
])
def test_soak_differential_stock_spill_pressure(tmp_path, sharded, pooled):
    """Keyed operator under the same soak: per-key min/max/mean survive
    spill pressure + restore, batched, pooled and (where possible)
    sharded."""
    results, (keys, ts, vals), totals = _drive(
        "stock", True, sharded, tmp_path, width=1, pooled=pooled)
    want = oracle_stock(keys, ts, vals, WINDOW, num_keys=8)
    assert set(results) == set(want)
    for wid, w in want.items():
        got = results[wid]
        present = w["min"] < np.inf
        np.testing.assert_allclose(got["mean"][present],
                                   w["mean"][present],
                                   rtol=2e-4, atol=2e-4, err_msg=str(wid))
        np.testing.assert_allclose(got["min"][present], w["min"][present],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got["max"][present], w["max"][present],
                                   rtol=1e-5, atol=1e-5)
    # spill pressure really happened: storage-tier traffic on both runs
    assert totals.ingested == N_EVENTS
    if pooled:
        assert totals.pooled_rows > 0


@pytest.mark.parametrize("pooled", [True, False])
def test_soak_differential_pipelined(tmp_path, pooled):
    """ISSUE 6: the pipelined engine — folds submitted to the async
    round worker while ingestion continues, per-slot epoch validation on
    the pooled path — must stay oracle-exact under the same lateness +
    spill + restore pressure, with zero silently-absorbed I/O failures.
    """
    results, (keys, ts, vals), totals = _drive(
        "average", True, False, tmp_path, pooled=pooled,
        pipelined=True)
    want = oracle_average(keys, ts, vals, WINDOW)
    assert set(results) == set(want)
    for wid in want:
        assert results[wid] == pytest.approx(want[wid], rel=2e-4,
                                             abs=2e-4), wid
    assert totals.ingested == N_EVENTS
    assert totals.ingested_late > N_EVENTS // 10
    # rounds really flowed through the async worker, and every task the
    # I/O executor ran either succeeded or would have raised (satellite:
    # no swallowed failures)
    assert totals.pipeline_rounds > 0
    assert totals.io_errors == 0
    if pooled:
        assert totals.pooled_rows > 0


@pytest.mark.parametrize("batched,pipelined", [
    (True, False), (True, True), (False, False),
])
def test_soak_differential_learned_prefetch(tmp_path, batched, pipelined):
    """ISSUE 7: the learned prefetch backend (lateness-model-driven
    segment sweeps + coalescing rewrites + WAL-coalesced commits) is a
    pure I/O-scheduling change — results must stay oracle-exact under
    the same lateness + spill + restore pressure."""
    results, (keys, ts, vals), totals = _drive(
        "average", batched, False, tmp_path, pipelined=pipelined,
        prefetch="learned")
    want = oracle_average(keys, ts, vals, WINDOW)
    assert set(results) == set(want)
    for wid in want:
        assert results[wid] == pytest.approx(want[wid], rel=2e-4,
                                             abs=2e-4), wid
    assert totals.ingested == N_EVENTS
    assert totals.ingested_late > N_EVENTS // 10
    assert totals.io_errors == 0


def _oracle_percentile(keys, ts, vals, qs=(0.5, 0.95, 0.99)):
    wstart = np.floor(ts / WINDOW) * WINDOW
    out = {}
    for s in np.unique(wstart):
        sel = wstart == s
        out[WindowId(float(s), float(s) + WINDOW)] = {
            q: float(np.quantile(vals[sel, 0], q)) for q in qs}
    return out


@pytest.mark.parametrize("sharded,pooled,splitk", [
    # ISSUE 8 axis: split-K chunked folds on/off over the pooled and
    # sharded layouts — results must be invariant to the decomposition
    (False, True, 8), (False, True, 0),
    (True, True, 8), (True, False, 8),
])
def test_soak_differential_splitk(tmp_path, sharded, pooled, splitk):
    """Split-K soak: chunked partial-accumulator folds under the full
    lateness + spill + restore pressure match the oracle exactly, and
    the chunked path really launched when enabled."""
    results, (keys, ts, vals), totals = _drive(
        "stock", True, sharded, tmp_path, width=1, pooled=pooled,
        splitk=splitk)
    want = oracle_stock(keys, ts, vals, WINDOW, num_keys=8)
    assert set(results) == set(want)
    for wid, w in want.items():
        got = results[wid]
        present = w["min"] < np.inf
        np.testing.assert_allclose(got["mean"][present],
                                   w["mean"][present],
                                   rtol=2e-4, atol=2e-4, err_msg=str(wid))
        np.testing.assert_allclose(got["min"][present], w["min"][present],
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(got["max"][present], w["max"][present],
                                   rtol=1e-5, atol=1e-5)
    assert totals.ingested == N_EVENTS
    if splitk and (pooled or (sharded and len(jax.devices()) > 1)):
        assert totals.splitk_launches > 0
    if not splitk:
        assert totals.splitk_launches == 0


@pytest.mark.parametrize("splitk", [0, 8])
def test_soak_differential_percentile(tmp_path, splitk):
    """ISSUE 8 satellite: percentile's real fold_batch (sorted-merge of
    per-chunk sorted runs) lets the blocking operator ride the batched
    path — the soak matrix no longer needs a fallback axis for it."""
    results, (keys, ts, vals), totals = _drive(
        "percentile", True, False, tmp_path, width=1, pooled=True,
        splitk=splitk)
    want = _oracle_percentile(keys, ts, vals)
    assert set(results) == set(want)
    for wid, w in want.items():
        for q, v in w.items():
            assert results[wid][q] == pytest.approx(v, rel=1e-5,
                                                    abs=1e-5), (wid, q)
    assert totals.ingested == N_EVENTS
    assert totals.batch_executions > 0       # percentile batched for real
    if splitk:
        assert totals.splitk_launches > 0


# --------------------------------------------------------------------------
# chaos axis (ISSUE 9): the full soak under injected store faults
# --------------------------------------------------------------------------

@pytest.mark.parametrize("pipelined", [True, False])
def test_soak_differential_chaos_faults(tmp_path, pipelined):
    """ISSUE 9 tentpole: the soak with >=5% injected store faults on the
    whole data path (get/put/commit/readahead). The retry layer absorbs
    every transient (max_consecutive=2 < io_retry_limit makes recovery
    deterministic), the degradation ladder sheds speculative work first,
    and final results still match the never-failing oracle exactly:
    zero lost windows, zero lost events."""
    results, (keys, ts, vals), totals = _drive(
        "average", True, False, tmp_path, pooled=True,
        pipelined=pipelined, fault_rate=0.25, fault_seed=77)
    want = oracle_average(keys, ts, vals, WINDOW)
    # oracle parity: identical window set, identical answers
    assert set(results) == set(want)
    for wid in want:
        assert results[wid] == pytest.approx(want[wid], rel=2e-4,
                                             abs=2e-4), wid
    assert totals.ingested == N_EVENTS          # zero lost events
    # the chaos really happened, and the retry layer really absorbed it
    assert totals.injected_faults > 100
    assert totals.io_retries > 0
    assert totals.io_gave_up == 0               # exact, by construction
    assert totals.io_staged_blocks > 0          # demand traffic survived
    # the ladder engaged, and engaged bottom-up: speculative readahead is
    # always the first thing shed, never demand traffic
    assert totals.ladder_transitions, "breaker never engaged"
    assert totals.ladder_transitions[0] == (0, 1)
    for frm, to in totals.ladder_transitions:
        assert abs(to - frm) == 1               # one rung at a time
    assert totals.shed_readahead_drives > 0
    # backpressure deferral (rung 4) may or may not be reached; if it
    # was, every deferred event must have been readmitted
    assert totals.deferred_events == totals.readmitted_events


def test_soak_differential_chaos_restart(tmp_path):
    """ISSUE 9 tentpole: a *permanent* store failure poisons the engine
    mid-run; ``EngineRecovery`` restores from the last manifest
    checkpoint (store reopen = WAL replay), the ledger replays events
    emitted after that checkpoint, and the run finishes with oracle
    parity -- better late than never, even through a restart."""
    from repro.core.buckets import Tier
    from repro.core.pipeline import PipelineError
    from repro.core.staging import StagingError
    from repro.storage import make_store

    store_dir = tmp_path / "chaos"
    inj = FaultInjector(seed=5,
                        rates={op: 0.05 for op in _CHAOS_OPS},
                        max_consecutive=2)

    def factory():
        inner = make_store("log", store_dir, segment_bytes=128 << 10)
        aion = AionConfig(block_size=256, batched_execution=True,
                          block_pool=True, pipelined_execution=True,
                          store_segment_bytes=128 << 10,
                          io_retry_backoff=0.0,
                          breaker_error_threshold=4)
        eng = StreamEngine(
            assigner=TumblingWindows(WINDOW),
            operator=make_operator("average", aion.block_size, 1),
            aion=aion, value_width=1,
            cleanup=_cleanup(),
            trigger=DeltaTTrigger(executions=2),
            # tiny budgets: even this short run spills to storage, so
            # the poisoned `get` is guaranteed to be on the fold path
            device_budget_bytes=1 << 16,
            host_budget_bytes=1 << 15,
            spill_dir=store_dir,
            store=FaultyBlockStore(inner, inj),
        )
        eng._fault_injector = inj
        return eng

    recovery = EngineRecovery(factory, max_restarts=3)
    rng = np.random.default_rng(SEED)
    eng = factory()
    n_events, chunk = 6000, 500
    ledger = []            # (start_index, batch, now): replay source
    all_events = []
    now, wm, emitted, chunks = 0.0, 0.0, 0, 0
    crashed = False

    def emit_chunk():
        nonlocal now, wm, emitted, chunks
        n = min(chunk, n_events - emitted)
        u = rng.random(n)
        delay = np.where(u < 0.65, rng.uniform(0.0, 2.0, n),
                         rng.uniform(0.0, MAX_LATE, n))
        ts = np.maximum(now - delay, 0.0)
        batch = EventBatch(rng.integers(0, 8, n), ts,
                           rng.normal(size=(n, 1)).astype(np.float32))
        all_events.append((batch.keys.copy(), batch.timestamps.copy(),
                           batch.values.copy()))
        ledger.append((emitted, batch, now))
        eng.ingest(batch, now)
        emitted += n
        chunks += 1
        if rng.random() < 0.7:
            wm = max(wm, now - rng.uniform(0.0, 5.0))
            eng.advance_watermark(wm, now)
        eng.poll(now)
        now += rng.uniform(1.0, 4.0)

    while emitted < n_events:
        emit_chunk()
        if chunks % 3 == 0:
            with inj.paused():          # checkpoints run clean
                recovery.checkpoint(eng, token=(emitted, now, wm))
        if not crashed and emitted >= n_events // 2:
            crashed = True
            # push all engine state to the persistent tier (cleanly), so
            # the next fold round MUST read through the store...
            with inj.paused():
                if eng.pipeline is not None:
                    eng.pipeline.drain()
                eng.io.drain()
                for st in eng.windows.values():
                    for blk in list(st.blocks):
                        if blk.tier == Tier.DEVICE:
                            eng.io.destage_block_sync(blk)
                eng.io.spill_blocks_sync(
                    [b for st in eng.windows.values() for b in st.blocks
                     if b.tier == Tier.HOST and b.fill > 0])
            # ...then poison it: every `get` now fails *permanently* --
            # the retry budget must NOT mask it (honest surfacing), the
            # round retry must NOT win, shutdown must raise
            inj.poison(("get",))
            with pytest.raises((PipelineError, StagingError)):
                eng.advance_watermark(now + MAX_LATE, now)
                eng.poll(now)
                eng.close()
            # the engine is dead; tear down its I/O cleanly and restore
            inj.heal()
            eng.pipeline.close()
            eng.io.drain(timeout=30.0)
            eng.io.shutdown()
            with inj.paused():
                eng, (ck_emitted, ck_now, ck_wm) = recovery.restore()
            now, wm = max(now, ck_now), ck_wm
            # better late than never: replay everything the checkpoint
            # does not cover (events land late, the engine folds them)
            for start, batch, b_now in ledger:
                if start >= ck_emitted:
                    eng.ingest(batch, now)
            eng.poll(now)

    assert crashed and recovery.restarts == 1
    wm = now + MAX_LATE
    eng.advance_watermark(wm, now)
    for t in np.linspace(now, now + 70.0, 8):
        eng.poll(t)
    _final_sweep(eng, now + 70.0)
    results = dict(eng.results)
    assert eng.io.stats["gave_up"] == 0
    assert eng.metrics.ingested > 0
    eng.close()

    keys = np.concatenate([k for k, _, _ in all_events])
    tss = np.concatenate([t for _, t, _ in all_events])
    vals = np.concatenate([v for _, _, v in all_events])
    want = oracle_average(keys, tss, vals, WINDOW)
    assert set(results) == set(want)            # zero lost windows
    for wid in want:
        assert results[wid] == pytest.approx(want[wid], rel=2e-4,
                                             abs=2e-4), wid
