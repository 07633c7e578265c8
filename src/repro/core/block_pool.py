"""Persistent device block pool: the arena behind the block-table fold.

The KV-cache idiom (flash-decoding's ``block_tables`` over a paged cache)
applied to Aion's m-bucket: instead of a per-block ``device_put`` whose
buffers are re-stacked into ``[rows, cap, W]`` tensors on every batched
fold, staging writes each block ONCE into a preallocated device arena —

    keys_arena    [pool_slots, block_capacity]      int32
    values_arena  [pool_slots, block_capacity, W]   float32

— at a free pool slot (a dynamic-update-slice), and the batched fold
consumes a *block table* of slot indices. Hot m-bucket blocks never leave
the arena between executions, so a batch over resident blocks launches
with zero per-batch copies: the gather is one take along the pool axis
(dense backend) or an in-kernel scalar-prefetch DMA (Mosaic backend).

Slot lifecycle (see ROADMAP "Persistent device block pool"):

    free -> filling -> resident -> folding -> destaged(free)

Concurrency contract (engine main thread + I/O executor thread):

* Arena updates are **in-place by default** (``dynamic_update_slice``
  with input donation — O(block) per fill, not O(arena)); computations
  already dispatched against the arena are protected by the runtime's
  buffer usage holds (a donation waits for in-flight readers), so a fold
  that is executing never observes a slot rewritten under it.
* What donation DOES invalidate is python-level references: donating
  deletes every live ``jax.Array`` alias of the old arena. The executor
  therefore brackets each snapshot -> fold-dispatch section with
  ``pinned()``; while any pin is held, writes take the **functional**
  (copy) path, so a pinned snapshot stays live until it has been handed
  to the runtime. Outside pins (ingest-time fills, destage churn) writes
  are donated and cheap.
* ``commit`` (write + ``block.pool_slot`` assignment) and
  ``snapshot_for`` (arena objects + slot reads) are atomic under the pool
  lock, so a snapshot either sees a slot with its data already in the
  captured arena, or no slot at all (the row falls back to the host
  path). ``release_slot`` clears ``block.pool_slot`` under the same lock,
  which makes a slot return to the free list exactly once even when a
  purge races an in-flight stage (both sides run under ``block.lock`` and
  surrender the slot through here).
* Timestamps are deliberately not pooled — no batch fold is
  time-dependent within a window (see the ``fold_batch`` contract); the
  host copy keeps them for checkpoints.

Slots partition into ``num_shards`` contiguous ranges for the slot-sharded
fold: a window's blocks are allocated in the range of the shard that
``distributed.sharding.shard_of_window`` assigns the window to, so the
block table a shard receives only ever references its own arena range
(the shard_map passes each device its ``[pool_slots/D, ...]`` arena tile).
Given the slot mesh, the arenas are placed with a ``NamedSharding`` over
their slot axis: each device holds its own range from the start, and
neither fills nor folds ever reshard them.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.obs import MetricsRegistry, StatsMap, Tracer


def _write_fn(k_arena, v_arena, slot, keys, values):
    return (jax.lax.dynamic_update_slice(k_arena, keys[None], (slot, 0)),
            jax.lax.dynamic_update_slice(v_arena, values[None], (slot, 0, 0)))


def _read_fn(k_arena, v_arena, slot):
    cap = k_arena.shape[1]
    w = v_arena.shape[2]
    return (jax.lax.dynamic_slice(k_arena, (slot, 0), (1, cap))[0],
            jax.lax.dynamic_slice(v_arena, (slot, 0, 0), (1, cap, w))[0])


_write_jit = jax.jit(_write_fn)
# donated variant: XLA aliases input -> output and updates the slot in
# place — O(block) per fill instead of an O(arena) copy. Platforms that
# cannot donate silently fall back to the copy (still correct).
_write_donated_jit = jax.jit(_write_fn, donate_argnums=(0, 1))
_read_jit = jax.jit(_read_fn)


def _scatter_fn(k_arena, v_arena, slots, keys, values):
    """Batched multi-slot commit: ONE scatter along the pool axis for a
    whole round of fills — O(arena + k*block) instead of k functional
    O(arena) copies when the pin forces the copy path."""
    return k_arena.at[slots].set(keys), v_arena.at[slots].set(values)


_scatter_jit = jax.jit(_scatter_fn)
_scatter_donated_jit = jax.jit(_scatter_fn, donate_argnums=(0, 1))


class DeviceBlockPool:
    """Preallocated device arena + per-shard slot free lists."""

    def __init__(self, pool_slots: int, block_capacity: int, width: int,
                 num_shards: int = 1,
                 max_arena_bytes: Optional[int] = None,
                 registry: Optional[MetricsRegistry] = None,
                 mesh=None, tracer: Optional[Tracer] = None):
        if mesh is not None:
            num_shards = mesh.size
        num_shards = max(int(num_shards), 1)
        pool_slots = max(int(pool_slots), num_shards)
        # round up to a multiple of the shard count so the arena splits
        # evenly under shard_map (P(axis) on the slot axis)
        pool_slots = -(-pool_slots // num_shards) * num_shards
        row_bytes = block_capacity * (4 + 4 * width)
        if max_arena_bytes is not None and row_bytes > 0:
            # round DOWN to the shard multiple: the arena must never
            # exceed max_arena_bytes (the engine's at-most-half-budget
            # guarantee for utilization-driven policies); a cap below
            # one slot per shard disables the pool entirely — callers
            # check ``pool_slots == 0`` and fall back to the legacy path
            fit = (max_arena_bytes // row_bytes) // num_shards * num_shards
            pool_slots = min(pool_slots, fit)
        self.pool_slots = pool_slots
        self.capacity = block_capacity
        self.width = width
        # physical device bytes the arenas occupy — charged ONCE against
        # the engine's device budget at construction; a pooled fill then
        # costs a slot, not a second per-block reservation (the legacy
        # device_put fallback still reserves per block)
        self.arena_bytes = pool_slots * row_bytes
        self.num_shards = num_shards
        self.slots_per_shard = pool_slots // num_shards
        self._lock = threading.Lock()
        self._pins = 0                     # live snapshot sections
        self._deferred = 0                 # live deferred-fill sections
        # slot -> (keys, values) commits buffered while deferred; flushed
        # as ONE batched scatter at the next snapshot/read
        self._pending: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._free: List[deque] = [
            deque(range(d * self.slots_per_shard,
                        (d + 1) * self.slots_per_shard))
            for d in range(num_shards)]
        self._rr = 0                       # round-robin for shard=None
        # per-slot epoch/sequence scheme (ROADMAP: carried from PR 4):
        # a slot's epoch bumps whenever its CONTENTS or OWNERSHIP change
        # (commit, release, free) — never on alloc, which only removes
        # the slot from the free list. The pipelined executor classifies
        # rows from an unpinned (slot, epoch) read, then re-validates the
        # pairs under a short pin at dispatch: an unchanged epoch proves
        # the captured arena holds exactly the data the row was
        # classified against, so the pin only needs to span
        # snapshot -> dispatch instead of the whole fold round (and
        # ingest-time fills in between donate in place, O(block)).
        self._slot_epoch: List[int] = [0] * pool_slots
        self.seq = 0                       # global epoch counter
        k_dev = v_dev = None
        if mesh is not None and num_shards > 1:
            axis = mesh.axis_names[0]
            k_dev = NamedSharding(mesh, P(axis, None))
            v_dev = NamedSharding(mesh, P(axis, None, None))
        self.keys = jnp.zeros((pool_slots, block_capacity), jnp.int32,
                              device=k_dev)
        self.values = jnp.zeros((pool_slots, block_capacity, width),
                                jnp.float32, device=v_dev)
        registry = registry if registry is not None else MetricsRegistry()
        self.registry = registry
        self.stats = StatsMap(registry, "aion_pool")
        self.stats.register_many([
            "allocs", "frees", "exhausted", "writes",
            "copy_writes", "deferred_fills",
            "batched_fill_commits", "epoch_bumps",
            # host seconds inside arena fills: per-block commits and
            # batched flushes, host copies and dispatch included
            "fill_seconds"])
        # ``pool.fill`` spans go to the engine's tracer (off by default)
        self.tracer = tracer if tracer is not None else Tracer()
        # occupancy gauges are cheaper polled than maintained: the
        # registry snapshot calls back into the pool under its lock
        registry.register_callback(lambda: {
            "aion_pool_free_slots": self.free_slots(),
            "aion_pool_slots": self.pool_slots,
            "aion_pool_arena_bytes": self.arena_bytes,
        })

    def _bump_epoch_locked(self, slot: int) -> None:
        self._slot_epoch[slot] += 1
        self.seq += 1
        self.stats.inc("epoch_bumps")

    @contextlib.contextmanager
    def deferred_fills(self):
        """Batch-commit lease for a fold round's cold fills: while held,
        ``commit`` buffers (slot, data) pairs instead of writing the
        arena per block, and the next ``snapshot_for``/``read_block`` —
        or the lease exit — flushes them as ONE batched scatter. Under a
        concurrent ``pinned()`` section each per-block commit would be a
        functional O(arena) copy; the batch makes a round of k fills
        O(arena + k*block). The buffered fills wait on the host and cross
        to the device as one stacked transfer at the flush, so a round's
        k fills never sit on the device twice (as k arrays and as their
        stack). Slot attachment stays immediate (a pending
        slot is resident for placement purposes); reads always flush
        first, so no path can observe a slot without its data."""
        with self._lock:
            self._deferred += 1
        try:
            yield
        finally:
            with self._lock:
                self._deferred -= 1
                if self._deferred == 0:
                    self._flush_pending_locked()

    def _flush_pending_locked(self) -> None:
        """One scatter commit for every buffered fill (caller holds the
        pool lock). Functional while pinned (snapshot references stay
        live), donated otherwise."""
        if not self._pending:
            return
        with self._filling(len(self._pending)):
            slots = list(self._pending)
            # pad the batch to a power of two by repeating the first
            # entry (same slot, same data: an idempotent duplicate
            # scatter row) so the jitted scatter sees O(log) distinct
            # shapes
            n = 1
            while n < len(slots):
                n <<= 1
            slots = slots + [slots[0]] * (n - len(slots))
            ks = np.stack([self._pending[s][0] for s in slots])
            vs = np.stack([self._pending[s][1] for s in slots])
            idx = np.asarray(slots, np.int32)
            scatter = _scatter_jit if self._pins else _scatter_donated_jit
            if self._pins:
                self.stats.inc("copy_writes")
            self.keys, self.values = scatter(self.keys, self.values, idx,
                                             ks, vs)
        self.stats.inc("batched_fill_commits")
        self._pending.clear()

    @contextlib.contextmanager
    def _filling(self, blocks: int):
        """One arena fill of ``blocks`` blocks: a ``pool.fill`` span
        under the filling thread's own, timed into
        ``stats['fill_seconds']``."""
        t0 = time.perf_counter()
        try:
            with self.tracer.inner("pool.fill", blocks=blocks):
                yield
        finally:
            self.stats.inc("fill_seconds", time.perf_counter() - t0)

    @contextlib.contextmanager
    def pinned(self):
        """Snapshot-stability lease: while any pin is held, arena writes
        take the functional (copy) path so python references returned by
        ``snapshot_for`` stay live. Bracket snapshot -> fold-dispatch
        sections with this; once the fold is dispatched the runtime's
        usage holds protect it and the pin can drop (letting overlapped
        demand fills write in place)."""
        with self._lock:
            self._pins += 1
        try:
            yield
        finally:
            with self._lock:
                self._pins -= 1

    # ------------------------------------------------------------ slot mgmt
    def shard_of_slot(self, slot: int) -> int:
        return slot // self.slots_per_shard

    def alloc(self, shard: Optional[int] = None) -> Optional[int]:
        """Take a free slot from ``shard``'s range (state: free -> filling).

        ``shard=None`` round-robins across shards (unsharded pools have a
        single shard, so this is simply "any slot"). A full shard range
        returns None — no cross-shard stealing, since a slot outside the
        window's shard range could never appear in that shard's block
        table; the caller falls back to the legacy device_put path.
        """
        with self._lock:
            if shard is None:
                for off in range(self.num_shards):
                    d = (self._rr + off) % self.num_shards
                    if self._free[d]:
                        self._rr = (d + 1) % self.num_shards
                        self.stats.inc("allocs")
                        return self._free[d].popleft()
                self.stats.inc("exhausted")
                return None
            d = shard % self.num_shards
            if not self._free[d]:
                self.stats.inc("exhausted")
                return None
            self.stats.inc("allocs")
            return self._free[d].popleft()

    def free(self, slot: int) -> None:
        """Return an unattached slot (alloc'd but never committed)."""
        with self._lock:
            self._pending.pop(slot, None)
            self._free[self.shard_of_slot(slot)].append(slot)
            self._bump_epoch_locked(slot)
            self.stats.inc("frees")

    def release_slot(self, block) -> Optional[int]:
        """Surrender ``block``'s slot back to the free list, exactly once.

        Callers hold ``block.lock`` (destage / drop / aborted stage), so
        concurrent surrenders serialize there; the None-check under the
        pool lock makes a double call harmless anyway. A buffered
        deferred fill for the slot is discarded — the block is leaving
        the device tier, its data must not land after the slot is
        reused.
        """
        with self._lock:
            slot = block.pool_slot
            if slot is None:
                return None
            block.pool_slot = None
            self._pending.pop(slot, None)
            self._free[self.shard_of_slot(slot)].append(slot)
            self._bump_epoch_locked(slot)
            self.stats.inc("frees")
            return slot

    def free_slots(self) -> int:
        with self._lock:
            return sum(len(f) for f in self._free)

    # ------------------------------------------------------------- transfers
    def commit(self, block, slot: int,
               host_data: Dict[str, np.ndarray]) -> None:
        """Write ``host_data`` into ``slot`` and attach it to ``block``
        (state: filling -> resident). Atomic vs ``snapshot_for`` so a
        snapshot never sees a slot whose data is not in its captured
        arena. Caller holds ``block.lock`` (the drop-race handoff) and
        passes the host arrays it validated — re-reading
        ``block.host_data`` here would race a concurrent spill that just
        nulled it (spill keeps the same bytes on storage, so committing
        the caller's snapshot stays correct, exactly like the legacy
        ``device_put`` path). Ingest appends to a host block only under
        ``block.lock`` too, so the copy taken here holds exactly the
        events the block's fill counts."""
        with self._filling(1):
            # private copies: the host arrays stay mutable (ingest
            # appends to a host block's tail) and a device transfer may
            # read its source after this returns, or alias it outright
            keys = np.array(host_data["keys"], np.int32)
            vals = np.array(host_data["values"], np.float32)
            with self._lock:
                if self._deferred:
                    # a fold round's fills batch into one host stack, one
                    # transfer and one scatter at the next snapshot/read
                    # (see ``deferred_fills``)
                    self._pending[slot] = (keys, vals)
                    self.stats.inc("deferred_fills")
                else:
                    write = _write_jit if self._pins \
                        else _write_donated_jit
                    if self._pins:
                        self.stats.inc("copy_writes")
                    self.keys, self.values = write(self.keys, self.values,
                                                   slot, keys, vals)
                block.pool_slot = slot
                block.pool = self
                self._bump_epoch_locked(slot)
                self.stats.inc("writes")

    def slot_epochs(self, blocks) -> List[Tuple[Optional[int], int]]:
        """One consistent ``(pool_slot, epoch)`` read per block — NO
        arena capture, NO pin required. The pipelined executor
        classifies rows from this, issues demand fills, and only then
        takes the short ``pinned()`` section: ``snapshot_with_epochs``
        re-reads the pairs under the pin, and any row whose pair moved
        (destaged, purged, slot recycled to another block) demotes to
        the stacked fallback instead of folding a stale slot."""
        with self._lock:
            out: List[Tuple[Optional[int], int]] = []
            for b in blocks:
                s = b.pool_slot
                out.append((s, self._slot_epoch[s]) if s is not None
                           else (None, -1))
            return out

    def snapshot_with_epochs(self, blocks) -> Tuple[
            jnp.ndarray, jnp.ndarray, List[Optional[int]], List[int]]:
        """``snapshot_for`` + the epoch of each block's slot, one atomic
        read. Call inside a ``pinned()`` section; comparing the returned
        (slot, epoch) pairs against an earlier ``slot_epochs`` read
        proves (or disproves) that the captured arena still holds the
        data each row was classified against."""
        with self._lock:
            self._flush_pending_locked()
            slots = [b.pool_slot for b in blocks]
            epochs = [self._slot_epoch[s] if s is not None else -1
                      for s in slots]
            return self.keys, self.values, slots, epochs

    def snapshot_for(self, blocks) -> Tuple[jnp.ndarray, jnp.ndarray,
                                            List[Optional[int]]]:
        """(keys_arena, values_arena, slot-per-block) — one consistent
        view. Call inside a ``pinned()`` section: while pinned, writes
        are functional so the returned references stay live; after the
        consuming fold is dispatched the pin can drop (usage holds take
        over) and subsequent writes may donate the buffers."""
        with self._lock:
            self._flush_pending_locked()
            return self.keys, self.values, [b.pool_slot for b in blocks]

    def read_block(self, block) -> Optional[Dict[str, jnp.ndarray]]:
        """Device view of one resident block ({keys, values}), or None if
        the block holds no slot. Used by the per-window fold path.

        The slice is dispatched UNDER the pool lock: once enqueued, the
        runtime's usage holds keep the read consistent even if a donated
        write lands right after — but a write between snapshot and
        dispatch would delete the reference, so the two must be atomic.
        """
        with self._lock:
            slot = block.pool_slot
            if slot is None:
                return None
            self._flush_pending_locked()
            k, v = _read_jit(self.keys, self.values, slot)
        return {"keys": k, "values": v}

    def read_host(self, block) -> Optional[Dict[str, np.ndarray]]:
        """Host copy of a resident block's pooled arrays (destage path
        when the host copy was lost)."""
        d = self.read_block(block)
        if d is None:
            return None
        out = {k: np.asarray(v) for k, v in d.items()}
        # timestamps are not pooled (no batch fold is time-dependent);
        # a defensively-rebuilt host copy carries zeros so the SoA schema
        # stays uniform for checkpoints
        out["timestamps"] = np.zeros((self.capacity,), np.float64)
        return out
