"""Persistent device block pool: slot lifecycle, exhaustion fallback,
purge/destage exactly-once slot frees, snapshot immutability, and
engine-level parity of the pooled batched path."""
import numpy as np
import pytest

from repro.configs.base import AionConfig
from repro.core import StreamEngine, TumblingWindows
from repro.core.block_pool import DeviceBlockPool
from repro.core.buckets import Block, MemoryBudget, Tier
from repro.core.events import EventBatch
from repro.core.operators import make_operator
from repro.core.staging import IOScheduler
from repro.core.triggers import DeltaTTrigger

CAP, W = 16, 1


def _block(key_val=1, fill=CAP):
    b = Block.new(CAP, W)
    b.host_data["keys"][:] = key_val
    b.host_data["values"][:] = float(key_val)
    b.fill = fill
    return b


# ------------------------------------------------------------ pool basics
def test_alloc_free_cycle_and_exhaustion():
    pool = DeviceBlockPool(4, CAP, W)
    slots = [pool.alloc() for _ in range(4)]
    assert sorted(slots) == [0, 1, 2, 3]
    assert pool.alloc() is None                  # exhausted, no crash
    assert pool.stats["exhausted"] == 1
    pool.free(slots[0])
    assert pool.alloc() == slots[0]


def test_commit_snapshots_host_arrays():
    """A pooled slot is a copy: the host arrays it came from stay
    mutable (ingest appends to a host block's tail), and a later write
    to them must never reach the arena."""
    pool = DeviceBlockPool(2, CAP, W)
    b = _block(3, fill=CAP // 2)
    pool.commit(b, pool.alloc(), b.host_data)
    b.host_data["values"][:] = 99.0
    b.host_data["keys"][:] = 7
    got = pool.read_host(b)
    assert np.all(got["values"] == 3.0) and np.all(got["keys"] == 3)


def test_sharded_ranges_no_cross_shard_stealing():
    pool = DeviceBlockPool(8, CAP, W, num_shards=4)
    assert pool.slots_per_shard == 2
    a = pool.alloc(shard=1)
    b = pool.alloc(shard=1)
    assert {pool.shard_of_slot(a), pool.shard_of_slot(b)} == {1}
    # shard 1 range full: no stealing from other shards (a foreign slot
    # could never appear in shard 1's block table)
    assert pool.alloc(shard=1) is None
    assert pool.alloc(shard=2) is not None


def test_commit_read_roundtrip():
    pool = DeviceBlockPool(4, CAP, W)
    blk = _block(7)
    slot = pool.alloc()
    with blk.lock:
        pool.commit(blk, slot, blk.host_data)
    assert blk.pool_slot == slot and blk.pool is pool
    d = pool.read_block(blk)
    np.testing.assert_array_equal(np.asarray(d["keys"]),
                                  blk.host_data["keys"])
    np.testing.assert_allclose(np.asarray(d["values"]),
                               blk.host_data["values"])


def test_snapshot_immutable_under_slot_reuse_while_pinned():
    """A pinned snapshot must survive its slot being freed, reused and
    rewritten — pinned writes take the functional (copy) path, so old
    arena references stay live and unchanged."""
    pool = DeviceBlockPool(1, CAP, W)
    a = _block(1)
    slot = pool.alloc()
    with a.lock:
        pool.commit(a, slot, a.host_data)
    with pool.pinned():
        k_arena, v_arena, slots = pool.snapshot_for([a])
        assert slots == [slot]
        pool.release_slot(a)
        b = _block(9)
        slot2 = pool.alloc()
        assert slot2 == slot                     # same physical slot
        with b.lock:
            pool.commit(b, slot2, b.host_data)
        assert pool.stats["copy_writes"] == 1    # pinned -> functional
        # the old snapshot still reads block a's data
        assert int(np.asarray(k_arena)[slot][0]) == 1
        # the pool's current arena reads block b's
        assert int(np.asarray(pool.keys)[slot][0]) == 9


def test_unpinned_writes_update_in_place():
    """Outside a pinned section, fills donate the arena buffers (O(block)
    updates); the pool's current view always reads the new data."""
    pool = DeviceBlockPool(2, CAP, W)
    a, b = _block(3), _block(5)
    for blk in (a, b):
        s = pool.alloc()
        with blk.lock:
            pool.commit(blk, s, blk.host_data)
    assert pool.stats["copy_writes"] == 0        # both writes donated
    for blk in (a, b):
        d = pool.read_block(blk)
        np.testing.assert_array_equal(np.asarray(d["keys"]),
                                      blk.host_data["keys"])


def test_deferred_fills_batch_into_one_scatter():
    """Inside ``deferred_fills`` commits buffer; the next snapshot/read
    flushes them as ONE batched scatter (k fills cost one arena commit,
    not k functional copies under a pin)."""
    pool = DeviceBlockPool(8, CAP, W)
    blocks = [_block(i + 1) for i in range(4)]
    with pool.pinned(), pool.deferred_fills():
        for blk in blocks:
            s = pool.alloc()
            with blk.lock:
                pool.commit(blk, s, blk.host_data)
        assert pool.stats["deferred_fills"] == 4
        assert pool.stats["batched_fill_commits"] == 0
        # reads flush first: no path observes a slot without its data
        d = pool.read_block(blocks[0])
        np.testing.assert_array_equal(np.asarray(d["keys"]),
                                      blocks[0].host_data["keys"])
        assert pool.stats["batched_fill_commits"] == 1
        assert pool.stats["copy_writes"] == 1     # pinned -> one copy
    for blk in blocks:
        d = pool.read_block(blk)
        np.testing.assert_array_equal(np.asarray(d["keys"]),
                                      blk.host_data["keys"])
    assert pool.stats["batched_fill_commits"] == 1  # nothing re-flushed


def test_deferred_fill_dropped_when_slot_released():
    """A purge racing a deferred fill discards the buffered write: the
    slot returns free and a later occupant is never overwritten."""
    pool = DeviceBlockPool(1, CAP, W)
    a, b = _block(3), _block(9)
    with pool.deferred_fills():
        slot = pool.alloc()
        with a.lock:
            pool.commit(a, slot, a.host_data)
        pool.release_slot(a)                 # purge wins the race
        slot2 = pool.alloc()
        assert slot2 == slot
        with b.lock:
            pool.commit(b, slot2, b.host_data)
    d = pool.read_block(b)
    np.testing.assert_array_equal(np.asarray(d["keys"]),
                                  b.host_data["keys"])  # b, not a


# --------------------------------------------------- exactly-once slot free
def test_purge_while_pooled_frees_slot_exactly_once():
    pool = DeviceBlockPool(4, CAP, W)
    blk = _block()
    slot = pool.alloc()
    with blk.lock:
        pool.commit(blk, slot, blk.host_data)
    blk.tier = Tier.DEVICE
    assert pool.free_slots() == 3
    blk.drop()
    assert pool.free_slots() == 4
    assert blk.pool_slot is None
    blk.drop()                                   # idempotent second drop
    assert pool.free_slots() == 4
    assert pool.stats["frees"] == 1


def test_destage_then_purge_single_free():
    aion = AionConfig(block_size=CAP, pool_slots=4)
    budget = MemoryBudget(1 << 20)
    pool = DeviceBlockPool(4, CAP, W)
    io = IOScheduler(budget, pool=pool)
    blk = _block()
    assert io.stage_block_sync(blk)
    assert blk.pool_slot is not None and blk.tier == Tier.DEVICE
    assert io.stats["pool_fills"] == 1
    io.destage_block_sync(blk)
    assert blk.pool_slot is None and blk.tier == Tier.HOST
    assert pool.free_slots() == 4
    blk.drop()                                   # slot already surrendered
    assert pool.free_slots() == 4
    assert pool.stats["frees"] == 1
    io.shutdown()


def test_stage_racing_drop_releases_own_slot_and_budget():
    """A stage whose block was dropped mid-transfer frees the slot it
    allocated and its budget reservation (the drop never saw the slot)."""
    budget = MemoryBudget(1 << 20)
    pool = DeviceBlockPool(4, CAP, W)
    io = IOScheduler(budget, pool=pool)
    blk = _block()
    blk.dropped = True                # drop landed while request queued
    assert io.stage_block_sync(blk) is False
    assert pool.free_slots() == 4
    assert budget.used_bytes == 0
    io.shutdown()


def test_arena_cap_never_exceeded_by_shard_rounding():
    """Regression: the arena-size clamp rounds DOWN to the shard
    multiple, so a sharded pool never exceeds max_arena_bytes (the
    engine's at-most-half-budget guarantee); below one slot per shard
    the pool disables itself."""
    row = CAP * (4 + 4 * W)
    p = DeviceBlockPool(256, CAP, W, num_shards=8,
                        max_arena_bytes=20 * row)
    assert p.pool_slots == 16                 # 20 rounded DOWN to 8|16
    assert p.arena_bytes <= 20 * row
    tiny = DeviceBlockPool(256, CAP, W, num_shards=8,
                           max_arena_bytes=5 * row)
    assert tiny.pool_slots == 0               # < 1 slot/shard: disabled


def test_concurrent_duplicate_stage_leaks_no_slot():
    """Regression: a prestage racing a demand stage of the same block
    (thread-pool ablation) must not orphan a pool slot — the loser of
    the commit race surrenders its duplicate and reports success."""
    import threading
    budget = MemoryBudget(1 << 20)
    pool = DeviceBlockPool(8, CAP, W)
    io = IOScheduler(budget, pool=pool)
    for _ in range(10):
        blk = _block()
        ts = [threading.Thread(target=io.stage_block_sync, args=(blk,))
              for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert blk.tier == Tier.DEVICE and blk.pool_slot is not None
        io.destage_block_sync(blk)
    assert pool.free_slots() == 8             # every slot came back
    assert budget.used_bytes == 0
    io.shutdown()


def test_commit_uses_caller_snapshot_not_host_data():
    """Regression: a spill can null ``block.host_data`` between a
    stage's host read and its commit; the commit must write the caller's
    snapshot rather than crash (and leak the slot + budget bytes)."""
    pool = DeviceBlockPool(2, CAP, W)
    blk = _block(4)
    slot = pool.alloc()
    hd = blk.host_data
    blk.host_data = None                  # the racing spill's effect
    with blk.lock:
        pool.commit(blk, slot, hd)
    d = pool.read_block(blk)
    np.testing.assert_array_equal(np.asarray(d["keys"]), hd["keys"])


def test_respilled_block_not_leaked_after_device_restage(tmp_path):
    """Regression: a spill candidate popped from the LRU while it is
    device-resident (stage keeps the host shadow) must not stay counted
    as unevictable host bytes — it un-accounts on the failed spill and
    re-registers at its next destage."""
    budget = MemoryBudget(1 << 20)
    pool = DeviceBlockPool(4, CAP, W)
    io = IOScheduler(budget, pool=pool, spill_dir=tmp_path,
                     host_budget_bytes=1 << 30)
    blk = _block()
    assert io.stage_block_sync(blk)
    io.destage_block_sync(blk)            # accounted + in the spill LRU
    assert io._host_bytes == blk.nbytes
    assert io.stage_block_sync(blk)       # back to device, shadow kept
    io.host_budget_bytes = 0
    io._maybe_spill()                     # pops blk; cannot spill (DEVICE)
    assert io._host_bytes == 0            # un-accounted, not leaked
    io.destage_block_sync(blk)            # re-accounts, re-registers,
    assert blk.tier == Tier.STORAGE       # and immediately spills
    assert io._host_bytes == 0
    io.shutdown()


def test_drain_waits_for_threadpool_tasks():
    """Regression: drain() must cover in-flight tasks in the
    sequential_io=False (thread-pool) mode too, where nothing ever
    enters the priority queue."""
    import time as _t
    io = IOScheduler(MemoryBudget(1 << 20), sequential_io=False)
    done = []

    def slow():
        _t.sleep(0.15)
        done.append(1)
    io.submit(0, slow)
    io.drain()
    assert done == [1]
    io.shutdown()


def test_pool_exhaustion_falls_back_to_device_put():
    budget = MemoryBudget(1 << 20)
    pool = DeviceBlockPool(1, CAP, W)
    io = IOScheduler(budget, pool=pool)
    b1, b2 = _block(1), _block(2)
    assert io.stage_block_sync(b1)
    assert b1.pool_slot is not None
    assert io.stage_block_sync(b2)               # pool full -> legacy path
    assert b2.pool_slot is None and b2.device_data is not None
    assert b2.tier == Tier.DEVICE
    assert io.stats["pool_fallbacks"] == 1
    # both read device-side through the batched gather helper
    for b in (b1, b2):
        d = io.fetch_block_arrays(b)
        np.testing.assert_array_equal(np.asarray(d["keys"]),
                                      b.host_data["keys"])
    io.shutdown()


# ------------------------------------------------------------ engine level
def _run_engine(pooled, pool_slots=256, overlap=True, budget=64 << 20,
                op_name="stock", seed=3):
    aion = AionConfig(block_size=64, batched_execution=True,
                      block_pool=pooled, pool_slots=pool_slots,
                      pool_overlap_prefetch=overlap)
    op = make_operator(op_name, 64, 1, **(
        {"num_keys": 8} if op_name == "stock" else {}))
    eng = StreamEngine(assigner=TumblingWindows(10.0), operator=op,
                       aion=aion, value_width=1,
                       device_budget_bytes=budget,
                       trigger=DeltaTTrigger(executions=2))
    rng = np.random.default_rng(seed)
    n = 2500
    b = EventBatch(rng.integers(0, 8, n), rng.uniform(0, 80.0, n),
                   rng.normal(size=(n, 1)).astype(np.float32))
    eng.ingest(b, now=0.0)
    eng.advance_watermark(80.0, now=80.0)
    late = EventBatch(rng.integers(0, 8, 600), rng.uniform(0, 70.0, 600),
                      rng.normal(size=(600, 1)).astype(np.float32))
    eng.ingest(late, now=81.0)
    for t in np.linspace(81, 81 + 2 * eng.cleanup.current_bound(), 15):
        eng.poll(t)
    results = dict(eng.results)
    metrics = eng.metrics
    eng.close()
    return results, metrics


def _assert_results_equal(got, want):
    assert set(got) == set(want)
    for wid in want:
        g, w = got[wid], want[wid]
        for k in w:
            np.testing.assert_allclose(np.asarray(g[k], np.float64),
                                       np.asarray(w[k], np.float64),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"{wid} {k}")


def test_pooled_engine_matches_unpooled():
    want, m_ref = _run_engine(False)
    got, m_pool = _run_engine(True)
    _assert_results_equal(got, want)
    assert m_pool.pooled_rows > 0                # table path actually ran
    assert m_ref.pooled_rows == 0


def test_pool_slot_exhaustion_engine_parity():
    """A pool far smaller than the working set degrades rows to the
    stacked fallback without changing any result."""
    want, _ = _run_engine(False)
    got, m = _run_engine(True, pool_slots=2)
    _assert_results_equal(got, want)
    assert m.fallback_rows > 0                   # fallback actually ran
    assert m.pooled_rows > 0


def test_overlap_prefetch_off_parity():
    """pool_overlap_prefetch=False: cold p-blocks read host-side (PR-3
    behaviour), no demand fills are issued from the executor."""
    want, _ = _run_engine(False)
    got, m = _run_engine(True, overlap=False, budget=192 << 10)
    _assert_results_equal(got, want)
    assert m.demand_pool_fills == 0


def test_overlap_prefetch_issues_demand_fills_under_pressure():
    want, _ = _run_engine(False)
    got, m = _run_engine(True, overlap=True, budget=192 << 10)
    _assert_results_equal(got, want)
    assert m.demand_pool_fills > 0


def test_checkpoint_restore_with_pooled_blocks():
    """Pooled blocks checkpoint their event data and restore host-side
    (device placement is re-decided after restart)."""
    aion = AionConfig(block_size=32, block_pool=True, pool_slots=64)
    op = make_operator("average", 32, 1)
    eng = StreamEngine(assigner=TumblingWindows(10.0), operator=op,
                       aion=aion, value_width=1,
                       device_budget_bytes=16 << 20,
                       trigger=DeltaTTrigger(executions=1))
    rng = np.random.default_rng(11)
    b = EventBatch(rng.integers(0, 4, 500), rng.uniform(0, 30.0, 500),
                   rng.normal(size=(500, 1)).astype(np.float32))
    eng.ingest(b, now=0.0)
    assert any(blk.pool_slot is not None
               for st in eng.windows.values() for blk in st.blocks)
    snap = eng.checkpoint_state()
    eng.close()

    eng2 = StreamEngine(assigner=TumblingWindows(10.0), operator=op,
                        aion=aion, value_width=1,
                        device_budget_bytes=16 << 20,
                        trigger=DeltaTTrigger(executions=1))
    eng2.restore_state(snap)
    total = sum(st.total_events for st in eng2.windows.values())
    assert total == 500
    eng2.advance_watermark(40.0, now=40.0)
    from repro.core.windows import WindowId
    for s in (0.0, 10.0, 20.0):
        sel = (b.timestamps >= s) & (b.timestamps < s + 10.0)
        if not sel.any():
            continue
        assert eng2.results[WindowId(s, s + 10.0)] == pytest.approx(
            float(np.mean(b.values[sel, 0])), rel=1e-4, abs=1e-4)
    eng2.close()


def test_pool_disabled_has_no_pool():
    aion = AionConfig(block_size=32, block_pool=False)
    op = make_operator("average", 32, 1)
    eng = StreamEngine(assigner=TumblingWindows(10.0), operator=op,
                       aion=aion, value_width=1,
                       trigger=DeltaTTrigger(executions=1))
    assert eng.pool is None and eng.io.pool is None
    eng.close()
