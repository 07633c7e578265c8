"""Batched multi-window execution: one device pass per poll/watermark.

Paper §3 orders work by a strict priority rule — live window executions
first, then late re-executions, with demand staging outranking speculative
pre-staging. The per-window reference path (``StreamEngine.
execute_window``) honors that rule one window at a time, paying a jit
dispatch per block per window; with many concurrent due windows (long
lateness horizons keep many past windows re-executing) the dispatch
overhead — not the fold FLOPs — dominates.

This module keeps the priority rule but batches *within* a priority
class: each ``advance_watermark`` gathers every newly-expired window into
one live batch, and each ``poll`` gathers every due late re-execution
into one late batch — live batches always run before late batches because
the engine calls them in that order, so the rule is preserved at batch
granularity. Re-execution stays a pure function of bucket contents, so
folding N windows in one pass is bitwise-equivalent to N independent
folds up to float associativity (parity-tested in
``tests/test_batch_exec.py`` and ``tests/test_slot_sharding.py``).

Row gathering — the **block-table path** (``AionConfig.block_pool``,
default on): blocks staged by ``core.staging`` live in a persistent
device arena (``core.block_pool``), so a batch over already-resident
blocks is assembled as a *table* of pool-slot indices — O(rows) Python
ints — and the operator's ``fold_batch(..., table=)`` gathers the event
tiles straight from the arena (an in-kernel scalar-prefetch DMA on the
Mosaic backend, one take along the pool axis on the dense backend):
**zero per-batch copies**. Cold p-blocks are demand-staged INTO the pool
at ``PRIO_DEMAND_STAGE`` and that I/O **overlaps** the fold of the
already-resident shard (``pool_overlap_prefetch``): the executor
dispatches the resident block table, waits for the fills, folds the
newly-filled slots as a second table, and merges the partial accumulators
(``WindowOperator.merge_acc``). Blocks that could not be pooled (slot or
budget exhaustion, overlap off) degrade to the legacy stacked gather.

The legacy **stacked path** (``block_pool=False``, and the pooled path's
per-row fallback) re-materializes each batch: m-bucket rows that already
live on the device are stacked with a device concat (``jnp.stack`` —
``AionConfig.device_stacking``; False restores the PR-1 host ``np.stack``
+ one ``device_put``) and cold p-blocks are read host-side through
``IOScheduler.fetch_block_host`` (accounted, simulated-cost-charged).

Multi-device slot sharding (``AionConfig.slot_sharding``): the unpooled
placement round-robins due windows onto device-local slot ranges and
packs rows shard-major padded to a common power-of-two count; the fold
runs under a psum-free ``shard_map`` over the slot axis. The POOLED
placement is hash-based instead (``distributed.sharding.shard_of_window``
— the same map the staging shard hint uses), because pool slots are
assigned at STAGING time, before any batch composition is known: placing
a window on its hash shard is what keeps its block-table rows local to
the device whose arena tile holds them. Rows whose pool slot lands
outside their window's shard (stale placement, cross-range restores) fall
back to the stacked gather rather than being misfolded.

Split-K chunk planning (``AionConfig.splitk_chunk_rows > 0``, operators
with ``supports_splitk``): instead of one stripe per window padded to the
next power of two, a round's pooled rows pad to a multiple of the chunk
size and decompose greedily into launch groups of {8, 4, 2, 1} chunks
(``_plan_table_groups``); each group folds through the split-K kernel
(fixed-shape per-chunk partials, merged on-device) and the cross-group
partial accumulators merge via ``WindowOperator.merge_acc``. Every launch
shape is drawn from a fixed repertoire of at most four, so batch-size
changes across rounds never recompile — the stripe path re-jits at every
new pow2 bucket. Under slot sharding the STACKED fold instead deals rows
round-robin across the mesh (``pack_rows_shard_major(balance=True)``) and
folds full per-slot partials per device — a skewed window's rows spread
over every device instead of serializing on its owner.
"""
from __future__ import annotations

import time as _time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.buckets import Tier, WindowState
from repro.core.windows import WindowId
from repro.kernels.segment_aggregate import (
    next_pow2, pack_rows_shard_major,
)


# largest split-K launch group, in chunks: greedy pow2 decomposition of a
# round's chunk count into groups of {8, 4, 2, 1} chunks caps the shape
# repertoire at four launch shapes total (e.g. 13 chunks -> 8 + 4 + 1)
_SPLITK_MAX_CHUNKS = 8


def _splitk_groups(num_rows: int, chunk: int) -> List[Tuple[int, int]]:
    """``(offset, rows)`` launch groups for ``num_rows`` rows padded to a
    multiple of ``chunk``: the chunk count decomposes greedily into groups
    of {8, 4, 2, 1} chunks, so every group has one of at most four sizes
    ``{1,2,4,8} * chunk`` (only the last group holds padding)."""
    groups = []
    off = 0
    remaining = -(-num_rows // chunk)
    while remaining:
        g = min(_SPLITK_MAX_CHUNKS, 1 << (remaining.bit_length() - 1))
        groups.append((off, g * chunk))
        off += g * chunk
        remaining -= g
    return groups


@dataclass
class BatchWorkItem:
    """One due window execution (live expiry or late re-execution)."""
    wid: WindowId
    state: WindowState
    late: bool


def snapshot_block_partition(state: WindowState):
    """Atomic (m, p) partition of a window's blocks.

    Shared by the per-window and batched execution paths — the
    double-fold hazard lives here: snapshot BOTH lists before issuing any
    staging request, otherwise the I/O thread can move a block
    device-side between the two snapshots and it would be folded twice.
    """
    m_snapshot = state.m_blocks()
    m_ids = {id(b) for b in m_snapshot}
    p_blocks = [b for b in state.blocks if id(b) not in m_ids]
    return m_snapshot, p_blocks


def tier_counts(*block_lists) -> Dict[str, int]:
    """Blocks by tier (``device`` / ``host`` / ``storage``) over the
    given lists: what an execution record says it folded from where."""
    counts = {t.value: 0 for t in Tier}
    for blocks in block_lists:
        for b in blocks:
            counts[b.tier.value] += 1
    return counts


def plan_slot_placement(num_windows: int, num_devices: int
                        ) -> Tuple[List[int], int, int]:
    """Round-robin due windows onto device-local slot ranges.

    Device ``d`` owns the contiguous global slot range
    ``[d*slots_per, (d+1)*slots_per)``; window ``i`` of the batch lands on
    device ``i % num_devices`` at local slot ``i // num_devices``.
    ``slots_per`` is padded to a power of two so the jitted fold sees
    O(log) distinct shapes. Returns ``(slot_of_window, num_slots,
    slots_per)``; ``num_devices <= 1`` degenerates to the unsharded
    identity placement.
    """
    if num_devices <= 1:
        ns = next_pow2(num_windows)
        return list(range(num_windows)), ns, ns
    slots_per = next_pow2(-(-num_windows // num_devices))
    slot_of = [(i % num_devices) * slots_per + i // num_devices
               for i in range(num_windows)]
    return slot_of, num_devices * slots_per, slots_per


def plan_slot_placement_pooled(wids: List[WindowId], num_devices: int
                               ) -> Tuple[List[int], int, int]:
    """Hash-based placement for the pooled sharded fold.

    A window's pool slots were allocated at staging time in the arena
    range of ``shard_of_window(...)`` — placement must agree with that
    map or every block-table row would be misplaced. Windows group by
    their hash shard; each shard's windows take consecutive local slots,
    padded to a common power-of-two ``slots_per``. Degenerates to the
    identity placement on one device.
    """
    if num_devices <= 1:
        return plan_slot_placement(len(wids), 1)
    from repro.distributed.sharding import shard_of_window
    shards = [shard_of_window(w.start, w.end, num_devices) for w in wids]
    counts = [0] * num_devices
    local = []
    for s in shards:
        local.append(counts[s])
        counts[s] += 1
    slots_per = next_pow2(max(counts + [1]))
    slot_of = [s * slots_per + l for s, l in zip(shards, local)]
    return slot_of, num_devices * slots_per, slots_per


class BatchExecutor:
    """Executes a set of due windows in one vectorized device pass."""

    def __init__(self, engine):
        self.engine = engine
        self._mesh = None
        self._mesh_resolved = False

    # ---------------------------------------------------------- slot mesh
    def _slot_mesh(self):
        """The 1-D slot mesh, or None (sharding off / single device)."""
        if self._mesh_resolved:
            return self._mesh
        self._mesh_resolved = True
        aion = self.engine.aion
        if getattr(aion, "slot_sharding", False):
            from repro.distributed.sharding import make_slot_mesh
            self._mesh = make_slot_mesh(aion.slot_shard_devices,
                                        aion.slot_shard_axis)
        return self._mesh

    @staticmethod
    def _stack(rows: List[Any], device: bool, dtype) -> Any:
        """Stack per-block rows into one [rows, ...] tensor.

        ``device=True``: a device concat — already-resident jax rows are
        consumed in place and host rows are transferred individually, so
        hot m-bucket blocks never round-trip through the host.
        ``device=False``: the PR-1 host stack (one contiguous device_put
        inside the jitted fold).
        """
        if device:
            return jnp.stack([r if isinstance(r, jax.Array)
                              else jnp.asarray(r) for r in rows])
        return np.stack([np.asarray(r, dtype) for r in rows])

    # ------------------------------------------------------------ execute
    def execute(self, items: List[BatchWorkItem], now: float,
                trace_parent=None) -> Dict[WindowId, Any]:
        """Fold all items in one device pass; returns results by window.

        Falls back to the per-window reference path when the operator has
        no batch contract or the batch is trivial (a single window gains
        nothing from stacking). An empty item list is a no-op — no
        degenerate [0, ...] tensors, no metrics.

        ``trace_parent`` is the submitting span (watermark advance, poll
        sweep or pipeline round) handed across threads EXPLICITLY — the
        fold-round span it parents carries launch-group/split-K counts
        and whether this round recompiled.
        """
        eng = self.engine
        op = eng.operator
        if not items:
            return {}
        if not op.supports_batch or len(items) == 1:
            return {it.wid: eng.execute_window(it.wid, now, it.late,
                                               trace_parent=trace_parent)
                    for it in items}

        span = eng.tracer.child(
            trace_parent, "fold_round", windows=len(items),
            late=sum(1 for it in items if it.late))
        # pre-round registry reads for per-round span deltas (only when
        # this round is actually sampled — the disabled path stays free)
        cache_fn = getattr(getattr(op, "fold_batch", None),
                           "_cache_size", None)
        cache0 = sk0 = pooled0 = fallback0 = demoted0 = 0
        if span.sampled:
            cache0 = cache_fn() if callable(cache_fn) else 0
            sk0 = eng.metrics.splitk_launches
            pooled0 = eng.metrics.pooled_rows
            fallback0 = eng.metrics.fallback_rows
            demoted0 = eng.metrics.epoch_demoted_rows

        with span:
            t0_ns = _time.time_ns()
            t0 = _time.time()
            # the round's lease on its windows: destages queued before it
            # yield instead of undoing its demand fills mid-round
            for it in items:
                it.state.folding += 1
            try:
                # 1. snapshot every window atomically (membership is
                #    fixed from here on: each block folds exactly once,
                #    whatever tier it moves to while the batch assembles)
                plans = [(it, sum(snapshot_block_partition(it.state), []))
                         for it in items]
                tiers = [tier_counts(blocks) for _, blocks in plans] \
                    if eng.tracer.enabled else ()

                mesh = self._slot_mesh()
                num_devices = mesh.size if mesh is not None else 1

                if eng.pool is not None:
                    results, slot_of, num_slots, dispatch_dt, gather_dt, \
                        ran_sharded = self._fold_pooled(plans, mesh,
                                                        num_devices)
                else:
                    results, slot_of, num_slots, dispatch_dt, gather_dt, \
                        ran_sharded = self._fold_stacked(plans, mesh,
                                                         num_devices)
            finally:
                for it in items:
                    it.state.folding -= 1

            # per-window bookkeeping, identical to execute_window
            out: Dict[WindowId, Any] = {}
            for i, (it, _) in enumerate(plans):
                result = results[slot_of[i]]
                it.state.result = result
                eng.results[it.wid] = result
                it.state.last_executed_at = now
                it.state.events_at_last_exec = it.state.total_events
                if it.late:
                    eng.metrics.late_executions += 1
                else:
                    eng.metrics.live_executions += 1
                out[it.wid] = result
                eng._post_execute_destage(it.wid, it.state, now)
            eng.metrics.exec_seconds += _time.time() - t0
            t1_ns = _time.time_ns()
            for (it, _), blocks in zip(plans, tiers):
                eng.metrics.executions.append({
                    "window": it.wid.start, "late": it.late,
                    "path": "round", "t0": t0_ns, "t1": t1_ns,
                    "blocks": blocks})
            eng.metrics.batch_executions += 1
            eng.metrics.batched_windows += len(plans)
            eng.metrics.batch_dispatch_seconds += dispatch_dt
            eng.metrics.batch_gather_seconds += gather_dt
            eng.metrics.batch_occupancy_series.append(len(plans))
            eng.metrics.fold_seconds.observe(dispatch_dt)
            if ran_sharded:
                eng.metrics.sharded_batch_executions += 1
            if span.sampled:
                cache1 = cache_fn() if callable(cache_fn) else 0
                span.set(
                    splitk_launches=eng.metrics.splitk_launches - sk0,
                    pooled_rows=eng.metrics.pooled_rows - pooled0,
                    fallback_rows=eng.metrics.fallback_rows - fallback0,
                    epoch_demoted_rows=(
                        eng.metrics.epoch_demoted_rows - demoted0),
                    recompiled=bool(cache1 > cache0),
                    sharded=ran_sharded,
                    dispatch_seconds=round(dispatch_dt, 6),
                    gather_seconds=round(gather_dt, 6))
                span.event("emit", results=len(out))
        return out

    # ------------------------------------------------------ splitk planning
    def _splitk_chunk(self, num_rows: int, num_devices: int) -> int:
        """Effective split-K chunk size for a round of ``num_rows`` rows,
        or 0 when disabled: the knob is off, the operator's accumulator
        cannot merge arbitrary row partials (``supports_splitk`` False),
        or the round is smaller than one chunk per device (chunking a
        sub-chunk round would only add merge overhead)."""
        op = self.engine.operator
        chunk = getattr(self.engine.aion, "splitk_chunk_rows", 0)
        if chunk <= 0 or not getattr(op, "supports_splitk", False):
            return 0
        if num_rows <= chunk * max(num_devices, 1):
            return 0
        return chunk

    def _plan_table_groups(self, rows, num_devices: int, slots_per: int,
                           chunk: Optional[int] = None):
        """Launch groups ``[(table, fills, slots, splitk)]`` for pooled
        (block, window_slot, pool_slot) rows.

        ``chunk`` is the round's split-K chunk (``_splitk_chunk`` of the
        round's whole row count, so the resident/staged split of a round
        never changes its launch shapes); None decides from ``rows``.
        Split-K disabled (or sharded — the sharded layout keeps the
        ownership packing and chunks per shard inside the kernel): one
        legacy pow2-padded group. Single-device split-K: rows pad to a
        chunk multiple (pool slot 0, fill 0 — invalid everywhere,
        including the ±inf min/max identities) and decompose into the
        ``_splitk_groups`` repertoire of at most four shapes regardless
        of batch size — zero recompiles as rounds vary, where the stripe
        path re-jits per pow2 bucket. Cross-group partials merge via
        ``op.merge_acc`` in the shared tail."""
        if chunk is None:
            chunk = self._splitk_chunk(len(rows), num_devices)
        if chunk == 0 or num_devices > 1:
            tbl, fills, slots = self._pack_table(rows, num_devices,
                                                 slots_per)
            return [(tbl, fills, slots, chunk)]
        groups = []
        for off, n in _splitk_groups(len(rows), chunk):
            part = rows[off:off + n]
            pad = [0] * (n - len(part))
            groups.append((
                jnp.asarray([ps for _, _, ps in part] + pad, jnp.int32),
                jnp.asarray([blk.fill for blk, _, _ in part] + pad,
                            jnp.int32),
                jnp.asarray([ws for _, ws, _ in part] + pad, jnp.int32),
                chunk))
        return groups

    def _fold_table_groups(self, groups, arena_data, num_slots, use_mesh,
                           accs):
        """Dispatch every launch group against one arena snapshot; the
        group accumulators append to ``accs`` (merged in the shared
        tail). Returns the host seconds spent dispatching them."""
        eng = self.engine
        op = eng.operator
        d0 = _time.time()
        for table, fills, slots, sk in groups:
            accs.append(op.fold_batch(arena_data, fills, slots, num_slots,
                                      mesh=use_mesh, table=table,
                                      splitk=sk))
            if sk:
                eng.metrics.splitk_launches += 1
        return _time.time() - d0

    def _stack_rows(self, rows, num_devices: int, slots_per: int,
                    balance: bool = False, pad_to: int = 0):
        """Stacked (data, fills, slots) tensors from (arrays, fill,
        window_slot) rows.

        Shard-major via the same packing helper the parity tests drive:
        rows group by owning shard and every shard pads to a common
        power-of-two row count (invalid rows: fill 0, slot = shard's
        base slot) so row counts divide the mesh and the jitted fold
        sees O(log) distinct shapes. ``num_devices == 1`` degenerates to
        the PR-1 layout (one group, rows padded to pow2). ``balance``
        deals rows round-robin across shards instead (the split-K
        layout): callers must fold through the row-balanced kernel,
        which has no ownership precondition; padding rows take slot 0
        with fill 0 — invalid everywhere. The stack carries keys +
        values only: no batch fold is time-dependent within a window,
        and stacking timestamps would force a D2H pull of every hot
        device-resident row (f64 on host, f32 on device — see the
        fold_batch contract). ``pad_to`` replaces the pow2 padding with a
        fixed row count (one split-K group of the unsharded fallback).
        """
        eng = self.engine
        cap = eng.aion.block_size
        w = eng.value_width
        per_shard, rows_per_shard = pack_rows_shard_major(
            [slot for _, _, slot in rows], num_devices, slots_per,
            balance=balance)
        if pad_to:
            rows_per_shard = pad_to
        pad_arrs = {
            "keys": np.zeros((cap,), np.int32),
            "values": np.zeros((cap, w), np.float32),
        }
        keys_rows, val_rows = [], []
        fills: List[int] = []
        slots: List[int] = []
        for d, idxs in enumerate(per_shard):
            base_slot = d * slots_per \
                if num_devices > 1 and not balance else 0
            for r in idxs:
                arrs, fill, slot = rows[r]
                keys_rows.append(arrs["keys"])
                val_rows.append(arrs["values"])
                fills.append(fill)
                slots.append(slot)
            for _ in range(rows_per_shard - len(idxs)):
                keys_rows.append(pad_arrs["keys"])
                val_rows.append(pad_arrs["values"])
                fills.append(0)
                slots.append(base_slot)
        device = getattr(eng.aion, "device_stacking", True)
        data = {
            "keys": self._stack(keys_rows, device, np.int32),
            "values": self._stack(val_rows, device, np.float32),
        }
        return (data, jnp.asarray(fills, jnp.int32),
                jnp.asarray(slots, jnp.int32))

    # ----------------------------------------------------- stacked gather
    def _fold_stacked(self, plans, mesh, num_devices):
        """Legacy gather: re-materialize the batch as stacked tensors
        (device concat of resident rows; host reads of cold p-blocks).

        With split-K on under a mesh (operator permitting), the layout
        switches to **row-balanced**: identity slot placement (no per-
        device slot inflation), rows dealt round-robin across devices,
        and the fold runs the balanced sharded kernel — full per-slot
        partials per device, merged after the shard_map — so a skewed
        window's rows never serialize on one device."""
        eng = self.engine
        op = eng.operator
        chunk = getattr(eng.aion, "splitk_chunk_rows", 0)
        balanced = num_devices > 1 and chunk > 0 \
            and getattr(op, "supports_splitk", False)
        if balanced:
            slot_of, num_slots, slots_per = plan_slot_placement(
                len(plans), 1)
        else:
            slot_of, num_slots, slots_per = plan_slot_placement(
                len(plans), num_devices)

        # gather block rows: (arrays, fill, slot) in plan order — with
        # one batched store readahead so cold p-blocks arrive via a
        # sequential segment sweep instead of per-block random reads
        g0 = _time.time()
        eng.io.readahead_blocks(
            [blk for _, blocks in plans for blk in blocks])
        rows: List[Tuple[Dict[str, Any], int, int]] = []
        for i, (it, blocks) in enumerate(plans):
            for blk in blocks:
                if blk.fill == 0:
                    continue
                arrs = eng.io.fetch_block_arrays(blk)
                if arrs is None:         # purged mid-gather
                    continue
                rows.append((arrs, blk.fill, slot_of[i]))

        ran_sharded = False
        dispatch_dt = 0.0
        if rows:
            data, fills, slots = self._stack_rows(rows, num_devices,
                                                  slots_per,
                                                  balance=balanced)
            gather_dt = _time.time() - g0
            dispatch_t0 = _time.time()
            results = op.run_batch(data, fills, slots, num_slots,
                                   mesh=mesh,
                                   splitk=chunk if balanced else 0)
            dispatch_dt = _time.time() - dispatch_t0
            ran_sharded = mesh is not None
            if balanced:
                eng.metrics.splitk_launches += 1
        else:
            gather_dt = _time.time() - g0
            # every window empty: finalize the identity accumulator
            results = [op.finalize(op.init_acc()) for _ in range(num_slots)]
        return results, slot_of, num_slots, dispatch_dt, gather_dt, ran_sharded

    # ------------------------------------------------------- pooled gather
    def _pack_table(self, rows, num_devices: int, slots_per: int):
        """Shard-major (table, fills, slots) arrays from (block,
        window_slot, pool_slot) rows, each shard padded to a common
        power-of-two row count (padding: the shard's base pool slot with
        fill 0 — in-range for the shard, invalid for the fold)."""
        pool = self.engine.pool
        per_shard, rows_per_shard = pack_rows_shard_major(
            [ws for _, ws, _ in rows], num_devices, slots_per)
        table: List[int] = []
        fills: List[int] = []
        slots: List[int] = []
        for d, idxs in enumerate(per_shard):
            base_slot = d * slots_per if num_devices > 1 else 0
            base_pool = d * pool.slots_per_shard if num_devices > 1 else 0
            for r in idxs:
                blk, wslot, ps = rows[r]
                table.append(ps)
                fills.append(blk.fill)
                slots.append(wslot)
            for _ in range(rows_per_shard - len(idxs)):
                table.append(base_pool)
                fills.append(0)
                slots.append(base_slot)
        return (jnp.asarray(table, jnp.int32),
                jnp.asarray(fills, jnp.int32),
                jnp.asarray(slots, jnp.int32))

    def _fold_pooled(self, plans, mesh, num_devices):
        """Block-table gather over the persistent pool.

        Three row classes, folded as up to three partial accumulators and
        merged (``op.merge_acc``):
          * resident rows — already in the arena: block table, zero-copy;
          * cold p-blocks — demand pool-fills at PRIO_DEMAND_STAGE whose
            I/O overlaps the resident fold; filled slots fold as a second
            block table, the rest degrade to the stacked fallback;
          * fallback rows — unpoolable (slot/budget exhaustion, misplaced
            shard, legacy device_data): the stacked gather, unsharded.
        """
        eng = self.engine
        op = eng.operator
        pool = eng.pool
        aion = eng.aion
        use_mesh = mesh if num_devices > 1 else None

        slot_of, num_slots, slots_per = plan_slot_placement_pooled(
            [it.wid for it, _ in plans], num_devices)

        g0 = _time.time()
        gather_dt = 0.0
        dispatch_dt = 0.0
        blocks: List[Tuple[Any, int]] = []        # (block, window index)
        for i, (it, blks) in enumerate(plans):
            for blk in blks:
                if blk.fill:
                    blocks.append((blk, i))
        # one split-K decision per round, from its whole row count
        chunk = self._splitk_chunk(len(blocks), num_devices)

        def well_placed(ps, i):
            return num_devices <= 1 or \
                pool.shard_of_slot(ps) == slot_of[i] // slots_per

        accs: List[Any] = []
        ran_sharded = False
        evs: List[Any] = []
        cold: List[Tuple[Any, int]] = []          # (block, window index)
        fallback: List[Tuple[Any, int]] = []      # (block, wslot)

        # Pin strategy. The legacy (synchronous) path holds ONE pool pin
        # across the whole round — including the demand-fill wait — so
        # every fill that lands mid-round pays the functional copy path.
        # Under the pipelined engine the per-slot epoch scheme
        # (``pool_slot_epochs``) shrinks the pins to the
        # snapshot->dispatch windows: rows are classified OUTSIDE any
        # pin from a (slot, epoch) read, re-validated under a short pin
        # at dispatch (an unchanged epoch proves the captured arena
        # holds the classified data; moved rows demote to the stacked
        # fallback), and the fill wait happens UNPINNED — ingest-time
        # and overlapped demand fills donate in place, O(block).
        epoch_mode = eng.pipeline is not None \
            and getattr(aion, "pool_slot_epochs", True)

        if epoch_mode:
            pairs = pool.slot_epochs([b for b, _ in blocks])
            pooled3: List[Tuple[Any, int, int, int]] = []
            for (blk, i), (ps, ep) in zip(blocks, pairs):
                if ps is not None and well_placed(ps, i):
                    pooled3.append((blk, i, ps, ep))
                elif ps is None and blk.tier != Tier.DEVICE \
                        and aion.pool_overlap_prefetch:
                    cold.append((blk, i))
                else:
                    fallback.append((blk, slot_of[i]))
            if cold:
                by_window: Dict[int, List[Any]] = {}
                for blk, i in cold:
                    by_window.setdefault(i, []).append(blk)
                for i, blks in by_window.items():
                    evs.append(eng.io.request_stage(plans[i][0].state,
                                                    blks, demand=True))
                eng.metrics.demand_pool_fills += len(cold)
                # wait UNPINNED, BEFORE the snapshot: under the
                # pipelined engine inter-round overlap comes from the
                # round queue (round k+1's prefetch staged during round
                # k's fold), so this wait is only the prefetch residual
                # — and folding resident + freshly-filled rows as ONE
                # table keeps the dispatch shape round-invariant (the
                # two-table split re-jits a new staged-table shape
                # whenever the prefetch residual changes). A failed
                # fill aborts the round (StagingError) instead of
                # folding stale tiers.
                w0 = _time.time()
                for ev in evs:
                    ev.wait(timeout=60)
                eng.metrics.batch_stall_seconds += _time.time() - w0
                for ev in evs:
                    ev.check()
                for (blk, i), (ps, ep) in zip(
                        cold, pool.slot_epochs([b for b, _ in cold])):
                    if ps is not None and well_placed(ps, i):
                        pooled3.append((blk, i, ps, ep))
                    else:       # fill could not take a slot: host path
                        fallback.append((blk, slot_of[i]))
            gather_dt += _time.time() - g0

            if pooled3:
                g0 = _time.time()
                # one short pin: capture + validate + pack + dispatch
                with pool.pinned():
                    k_arena, v_arena, ps_now, ep_now = \
                        pool.snapshot_with_epochs(
                            [b for b, _, _, _ in pooled3])
                    pooled: List[Tuple[Any, int, int]] = []
                    for (blk, i, ps, ep), ps2, ep2 in zip(
                            pooled3, ps_now, ep_now):
                        if ps2 == ps and ep2 == ep:
                            pooled.append((blk, slot_of[i], ps))
                        else:
                            # destaged/purged/recycled since the
                            # classify read: fold the block's current
                            # truth through the stacked fallback
                            eng.metrics.epoch_demoted_rows += 1
                            fallback.append((blk, slot_of[i]))
                    if pooled:
                        groups = self._plan_table_groups(
                            pooled, num_devices, slots_per, chunk)
                        arena_data = {"keys": k_arena, "values": v_arena}
                        gather_dt += _time.time() - g0
                        dispatch_dt += self._fold_table_groups(
                            groups, arena_data, num_slots, use_mesh,
                            accs)
                        ran_sharded = ran_sharded or use_mesh is not None
                        eng.metrics.pooled_rows += len(pooled)
                    else:
                        gather_dt += _time.time() - g0
            return self._fold_pooled_tail(
                plans, accs, fallback, slot_of, num_slots, dispatch_dt,
                gather_dt, ran_sharded, chunk)

        # the whole batch runs under ONE pool pin: any fill that lands
        # while a fold may be executing takes the functional (copy) path,
        # which (a) keeps our snapshot references live and (b) never
        # touches the buffer the fold is reading — a donated in-place
        # write here would WAIT on the fold's usage hold and serialize
        # the overlap away. Fills outside a batch (ingest, pre-staging)
        # see no pin and write donated (O(block), in place).
        # deferred_fills batches the round's cold fills into ONE scatter
        # commit at the second snapshot — k overlapped fills cost
        # O(arena + k*block), not k functional O(arena) copies.
        with pool.pinned(), pool.deferred_fills():
            k_arena, v_arena, pslots = pool.snapshot_for(
                [b for b, _ in blocks])
            arena_data = {"keys": k_arena, "values": v_arena}

            pooled: List[Tuple[Any, int, int]] = []  # (blk, wslot, pslot)
            for (blk, i), ps in zip(blocks, pslots):
                if ps is not None and well_placed(ps, i):
                    pooled.append((blk, slot_of[i], ps))
                elif ps is None and blk.tier != Tier.DEVICE \
                        and aion.pool_overlap_prefetch:
                    cold.append((blk, i))
                else:
                    fallback.append((blk, slot_of[i]))

            # demand pool-fills for cold p-blocks: issued BEFORE the
            # resident fold so the I/O executor stages while the device
            # folds (the paper's demand-staging-outranks-prestaging rule,
            # at pool granularity)
            if cold:
                by_window = {}
                for blk, i in cold:
                    by_window.setdefault(i, []).append(blk)
                for i, blks in by_window.items():
                    evs.append(eng.io.request_stage(plans[i][0].state,
                                                    blks, demand=True))
                eng.metrics.demand_pool_fills += len(cold)
            gather_dt += _time.time() - g0

            if pooled:
                g0 = _time.time()
                groups = self._plan_table_groups(pooled, num_devices,
                                                 slots_per, chunk)
                gather_dt += _time.time() - g0
                dispatch_dt += self._fold_table_groups(
                    groups, arena_data, num_slots, use_mesh, accs)
                ran_sharded = ran_sharded or use_mesh is not None
                eng.metrics.pooled_rows += len(pooled)

            if evs:
                w0 = _time.time()
                for ev in evs:
                    ev.wait(timeout=60)
                eng.metrics.batch_stall_seconds += _time.time() - w0
                for ev in evs:
                    ev.check()       # failed demand fill aborts the round
                g0 = _time.time()
                k2, v2, ps2 = pool.snapshot_for([b for b, _ in cold])
                staged: List[Tuple[Any, int, int]] = []
                for (blk, i), ps in zip(cold, ps2):
                    if ps is not None and well_placed(ps, i):
                        staged.append((blk, slot_of[i], ps))
                    else:
                        # fill failed (budget/pool exhaustion) or landed
                        # in a foreign range: the stacked fallback reads
                        # it (device-preferred, host-accounted)
                        fallback.append((blk, slot_of[i]))
                gather_dt += _time.time() - g0
                if staged:
                    g0 = _time.time()
                    groups = self._plan_table_groups(
                        staged, num_devices, slots_per, chunk)
                    arena2 = {"keys": k2, "values": v2}
                    gather_dt += _time.time() - g0
                    dispatch_dt += self._fold_table_groups(
                        groups, arena2, num_slots, use_mesh, accs)
                    ran_sharded = ran_sharded or use_mesh is not None
                    eng.metrics.pooled_rows += len(staged)

        return self._fold_pooled_tail(plans, accs, fallback, slot_of,
                                      num_slots, dispatch_dt, gather_dt,
                                      ran_sharded, chunk)

    def _fold_pooled_tail(self, plans, accs, fallback, slot_of, num_slots,
                          dispatch_dt, gather_dt, ran_sharded, chunk=0):
        """Shared tail of both pooled pin strategies: fold the fallback
        rows through the stacked gather, then merge the partial
        accumulators into per-slot results. Under a split-K round
        (``chunk > 0``) the fallback rows stack in the same fixed
        ``_splitk_groups`` sizes as the table groups, so a fallback count
        that varies with tier timing never compiles a new shape."""
        eng = self.engine
        op = eng.operator
        if fallback:
            g0 = _time.time()
            rows = []
            eng.io.readahead_blocks([blk for blk, _ in fallback])
            for blk, wslot in fallback:
                arrs = eng.io.fetch_block_arrays(blk)
                if arrs is None:          # purged mid-gather
                    continue
                rows.append((arrs, blk.fill, wslot))
            if rows:
                # unsharded fold (any global slot id is valid on one
                # device), rows pow2-padded by the shared stacker or cut
                # into the round's split-K group sizes
                groups = _splitk_groups(len(rows), chunk) if chunk \
                    else [(0, 0)]
                for off, n in groups:
                    part = rows[off:off + n] if n else rows
                    data, fills, slots = self._stack_rows(
                        part, 1, num_slots, pad_to=n)
                    gather_dt += _time.time() - g0
                    d0 = _time.time()
                    accs.append(op.fold_batch(data, fills, slots,
                                              num_slots, mesh=None))
                    dispatch_dt += _time.time() - d0
                    g0 = _time.time()
                eng.metrics.fallback_rows += len(rows)
            else:
                gather_dt += _time.time() - g0

        if not accs:
            # every window empty: finalize the identity accumulator
            results = [op.finalize(op.init_acc()) for _ in range(num_slots)]
        else:
            d0 = _time.time()
            acc = accs[0]
            for a in accs[1:]:
                acc = op.merge_acc(acc, a)
            results = op.finalize_batch(acc, num_slots)
            dispatch_dt += _time.time() - d0
        return results, slot_of, num_slots, dispatch_dt, gather_dt, ran_sharded
