import time
from pathlib import Path

import numpy as np
import pytest

from repro.core.buckets import Block, MemoryBudget, Tier, WindowState
from repro.core.events import EventBatch
from repro.core.staging import (
    IOScheduler, PRIO_DESTAGE, PRIO_LATE_WRITE, PRIO_STAGE,
)


def _batch(n, width=2, seed=0):
    rng = np.random.default_rng(seed)
    return EventBatch(rng.integers(0, 8, n), rng.uniform(0, 100, n),
                      rng.normal(size=(n, width)).astype(np.float32))


def test_block_append_and_view():
    blk = Block.new(capacity=10, width=2)
    b = _batch(7)
    taken = blk.append(b, 0)
    assert taken == 7 and blk.fill == 7 and not blk.full
    view = blk.as_event_batch()
    np.testing.assert_array_equal(view.keys, b.keys)


def test_window_state_appends_across_blocks():
    st = WindowState(0.0, 10.0, width=2, block_capacity=16)
    st.append_events(_batch(40), late=False)
    assert st.total_events == 40
    assert len(st.blocks) == 3
    assert [b.fill for b in st.blocks] == [16, 16, 8]
    # append fills the partial tail block first
    st.append_events(_batch(10, seed=1), late=True)
    assert [b.fill for b in st.blocks][:3] == [16, 16, 16]
    assert st.late_events == 10


def test_memory_budget_accounting():
    mb = MemoryBudget(1000)
    assert mb.try_reserve(600)
    assert not mb.try_reserve(600)
    mb.release(600)
    assert mb.try_reserve(600)
    assert mb.peak_bytes == 600


def test_stage_destage_roundtrip():
    budget = MemoryBudget(10 << 20)
    io = IOScheduler(budget, sequential_io=True)
    st = WindowState(0, 10, width=2, block_capacity=32)
    st.append_events(_batch(100), late=False)
    ref = [b.as_event_batch().values.copy() for b in st.blocks]

    io.request_stage(st).wait(5)
    assert all(b.tier == Tier.DEVICE for b in st.blocks)
    assert budget.used_bytes == sum(b.nbytes for b in st.blocks)

    io.request_destage(st).wait(5)
    io.drain()
    assert all(b.tier == Tier.HOST for b in st.blocks)
    assert budget.used_bytes == 0
    for b, r in zip(st.blocks, ref):
        np.testing.assert_array_equal(
            b.as_event_batch().values, r[:b.fill])
    io.shutdown()


def test_destage_keeps_bootstrap_blocks():
    budget = MemoryBudget(10 << 20)
    io = IOScheduler(budget)
    st = WindowState(0, 10, width=1, block_capacity=16)
    st.append_events(_batch(64, width=1), late=False)
    io.request_stage(st).wait(5)
    io.request_destage(st, keep_bootstrap=2).wait(5)
    io.drain()
    tiers = [b.tier for b in st.blocks]
    assert tiers.count(Tier.DEVICE) == 2          # rho_min bootstrap set
    assert tiers[:2] == [Tier.DEVICE, Tier.DEVICE]  # initial events kept
    io.shutdown()


def test_priority_order_stage_before_destage():
    """Staging requests queued after a destage must run first."""
    budget = MemoryBudget(100 << 20)
    io = IOScheduler(budget, chunk_blocks=1)
    order = []
    io.submit(PRIO_DESTAGE, lambda: (time.sleep(0.02), order.append("d1")))
    io.submit(PRIO_DESTAGE, lambda: order.append("d2"))
    io.submit(PRIO_LATE_WRITE, lambda: order.append("w"))
    io.submit(PRIO_STAGE, lambda: order.append("s"))
    io.drain()
    # d1 was already running; among the queued rest: stage > write > destage
    assert order.index("s") < order.index("w") < order.index("d2")
    io.shutdown()


def test_storage_spill_roundtrip(tmp_path):
    budget = MemoryBudget(10 << 20)
    io = IOScheduler(budget, spill_dir=tmp_path)
    st = WindowState(0, 10, width=3, block_capacity=32)
    st.append_events(_batch(32, width=3), late=False)
    blk = st.blocks[0]
    ref = blk.as_event_batch().values.copy()
    io.spill_block_sync(blk)
    assert blk.tier == Tier.STORAGE and blk.host_data is None
    assert blk.storage_path is not None and blk.storage_path.exists()
    np.testing.assert_array_equal(blk.as_event_batch().values, ref)
    io.shutdown()


def test_spill_keeps_host_copy_of_block_that_grew_mid_spill(tmp_path):
    """Ingest may append to a host block's tail while the I/O thread
    spills it. The record written before the append misses the new
    events, so the spill must keep the host copy (and spill it again
    later) instead of dropping it."""
    from repro.storage import make_store
    store = make_store("log", tmp_path)
    io = IOScheduler(MemoryBudget(10 << 20), store=store)
    st = WindowState(0, 10, width=2, block_capacity=32)
    st.append_events(_batch(20), late=False)
    blk = st.blocks[0]
    late = _batch(5, seed=1)
    commit = store.commit

    def commit_then_append(*a, **kw):
        out = commit(*a, **kw)
        st.append_events(late, late=True)     # between put and finalize
        return out
    store.commit = commit_then_append
    io.spill_blocks_sync([blk])
    assert blk.tier == Tier.HOST and blk.fill == 25
    store.commit = commit
    io.spill_blocks_sync([blk])
    assert blk.tier == Tier.STORAGE
    np.testing.assert_array_equal(blk.as_event_batch().values[20:],
                                  late.values)
    io.shutdown()


def test_drop_removes_storage_file(tmp_path):
    budget = MemoryBudget(10 << 20)
    io = IOScheduler(budget, spill_dir=tmp_path)
    st = WindowState(0, 10, width=1, block_capacity=16)
    st.append_events(_batch(16, width=1), late=False)
    blk = st.blocks[0]
    io.spill_block_sync(blk)
    path = blk.storage_path
    freed, device_bytes = st.drop_all()
    assert freed > 0 and not path.exists()
    assert device_bytes == 0          # block was in storage, not on device
    io.shutdown()
