"""Windowed operators: the paper's evaluation workloads as block folds.

Operators consume window state *block by block* from the m-bucket (lazy
iteration): non-blocking operators fold incrementally so compute overlaps
staging; blocking operators (§3.3) must see the whole window before
finalizing. Folds are jit-compiled over fixed block shapes.

  average      non-blocking  mean of a stream of numbers
  bigrams      non-blocking  co-occurrence counts over token payloads
                             (2-3 orders more compute, like the paper)
  stock        non-blocking  per-symbol rolling min/max/mean + 5% alerts
  lrb          non-blocking  Linear Road: per-segment vehicle counts, avg
                             speed, accident detection -> toll
  percentile   BLOCKING      exact percentiles (needs the full window)

Batched contract: operators may additionally implement ``fold_batch`` /
``finalize_batch`` — a vectorized path that folds the blocks of MANY
windows in one device pass by reducing over composite ``(window_slot,
key)`` segment ids through the batched segment-aggregate kernel.
All five operators implement it — including the blocking ``percentile``,
whose accumulator is a per-slot sorted run merged by sorted-merge.

  fold_batch(data, fills, slots, num_slots, mesh=None, table=None,
             splitk=0) -> acc
      data   table is None: {"keys": [B, cap] i32, "values": [B, cap, W]
             f32} — B stacked blocks, padded (the legacy device-concat /
             host-stack gather).
             table given: the persistent pool ARENAS — {"keys":
             [pool_slots, cap] i32, "values": [pool_slots, cap, W] f32};
             rows are *referenced* by the table, never stacked.
             Timestamps are deliberately NOT part of either layout: no
             batch fold is time-dependent within a window, and carrying
             them would pull every hot device-resident row back to the
             host (f64 host-side, f32 once staged). A future time-aware
             operator must extend the executor's gather.
      fills  [B] i32   valid events per block (ragged fills)
      slots  [B] i32   block row -> window slot (several blocks of one
                       window share a slot)
      mesh   optional 1-D device mesh (static): slot-sharded execution —
             rows arrive shard-major, slots partition across devices, and
             the kernel gathers per-slot tiles with no cross-device
             reduction (see kernels.segment_aggregate)
      table  optional [B] i32 pool-slot indices (the block-table path):
             the fold gathers event tiles straight from the arena —
             in-kernel on the Mosaic backend, one take along the pool
             axis on the dense backend (zero per-batch host copies)
      splitk optional chunk size (static): > 0 routes block-table folds
             through the split-K kernel (fixed-shape chunks of ``splitk``
             rows, per-chunk partial accumulators merged on-device), and
             with a mesh routes stacked folds through the row-balanced
             sharded variant. Operators whose fold cannot reduce into
             plain per-slot partials must ignore it and declare
             ``supports_splitk=False`` (the bigram scatter masks rows by
             slot ownership — balanced rows would be silently dropped).
  finalize_batch(acc, num_slots) -> [per-window result] * num_slots
      element i is equal (up to float assoc.) to the per-window
      ``finalize(fold(...))`` over slot i's blocks.
  merge_acc(a, b) -> acc
      combines two partial batch accumulators over the SAME slot layout —
      what lets the executor fold the already-resident block table while
      demand pool-fills are in flight (then fold the newly-filled slots
      and merge), and what merges the split-K executor's per-chunk-group
      partials. Default (``default_merge_acc``): dict values merge by
      key — 'min' -> elementwise minimum, 'max' -> maximum, everything
      else adds; correct for every built-in *reduction* accumulator.
      Accumulators with a different merge identity MUST override via the
      ``merge`` field — percentile's sorted runs concatenate and re-sort
      (adding them would corrupt the state).
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def default_merge_acc(a: Dict[str, Any], b: Dict[str, Any]
                      ) -> Dict[str, Any]:
    """Combine two partial batch accumulators (dicts of per-slot arrays):
    'min' -> elementwise minimum, 'max' -> maximum, everything else adds.
    Every built-in batch accumulator conforms (sums, counts, extrema)."""
    out = {}
    for k in a:
        if k == "min":
            out[k] = jnp.minimum(a[k], b[k])
        elif k == "max":
            out[k] = jnp.maximum(a[k], b[k])
        else:
            out[k] = a[k] + b[k]
    return out


@dataclass
class WindowOperator:
    name: str
    blocking: bool
    init_acc: Callable[[], Any]
    fold: Callable[[Any, Dict[str, jnp.ndarray], jnp.ndarray], Any]
    finalize: Callable[[Any], Any]
    # vectorized multi-window contract (see module docstring); None ->
    # the engine falls back to per-window execution for this operator
    fold_batch: Optional[Callable[..., Any]] = None
    finalize_batch: Optional[Callable[[Any, int], list]] = None
    # partial-accumulator combine for the overlapped pooled fold; None ->
    # ``default_merge_acc`` (dict accs merging by key semantics)
    merge: Optional[Callable[[Any, Any], Any]] = None
    # split-K safety: True when fold_batch reduces into plain per-slot
    # partial accumulators, so rows may be chunked/balanced arbitrarily
    # and partials merged via merge_acc. False for folds that mask rows
    # by slot ownership (the big-vocab bigram scatter) — the executor
    # must not balance their rows or chunk their tables.
    supports_splitk: bool = False

    @property
    def supports_batch(self) -> bool:
        return self.fold_batch is not None and \
            self.finalize_batch is not None

    def merge_acc(self, a: Any, b: Any) -> Any:
        if self.merge is not None:
            return self.merge(a, b)
        return default_merge_acc(a, b)

    def run(self, blocks, fills) -> Any:
        """Reference path: fold over (block_data, fill) pairs."""
        acc = self.init_acc()
        for data, fill in zip(blocks, fills):
            acc = self.fold(acc, data, fill)
        return self.finalize(acc)

    def run_batch(self, data, fills, slots, num_slots: int,
                  mesh=None, table=None, splitk: int = 0) -> list:
        """Batched path: one device pass over the blocks of many windows;
        returns one finalized result per slot. ``mesh`` routes the fold
        through the slot-sharded multi-device kernel; ``table`` switches
        ``data`` from stacked rows to the pool arenas; ``splitk`` chunks
        the fold into fixed-shape partials (the contract requires
        fold_batch to accept all three, defaults None/0)."""
        assert self.supports_batch
        acc = self.fold_batch(data, fills, slots, num_slots, mesh=mesh,
                              table=table, splitk=splitk)
        return self.finalize_batch(acc, num_slots)


def _valid_mask(n: int, fill) -> jnp.ndarray:
    return jnp.arange(n) < fill


def _batch_valid(cap: int, fills) -> jnp.ndarray:
    """[B, cap] ragged-fill mask from per-block fills."""
    return jnp.arange(cap)[None, :] < fills[:, None]


def _per_slot_finalize(finalize: Callable[[Any], Any]):
    """finalize_batch from a per-window finalize: slice the batched acc
    (dict of [num_slots, ...] arrays) per slot and finalize each."""
    def finalize_batch(acc, num_slots):
        acc = {k: np.asarray(v) for k, v in acc.items()}
        return [finalize({k: v[i] for k, v in acc.items()})
                for i in range(num_slots)]
    return finalize_batch


# ------------------------------------------------------------------ average

def make_average(block_capacity: int, width: int) -> WindowOperator:
    from repro.kernels import (
        segment_aggregate_batched, segment_aggregate_block_table,
        segment_aggregate_block_table_splitk,
    )

    def init_acc():
        return {"sum": jnp.zeros((), jnp.float32),
                "count": jnp.zeros((), jnp.float32)}

    @jax.jit
    def fold(acc, data, fill):
        mask = _valid_mask(data["values"].shape[0], fill)
        v = jnp.where(mask, data["values"][:, 0], 0.0)
        return {"sum": acc["sum"] + jnp.sum(v, dtype=jnp.float32),
                "count": acc["count"] + jnp.sum(mask, dtype=jnp.float32)}

    def finalize(acc):
        return float(acc["sum"] / jnp.maximum(acc["count"], 1.0))

    @partial(jax.jit, static_argnames=("num_slots", "mesh", "splitk"))
    def fold_batch(data, fills, slots, num_slots, mesh=None, table=None,
                   splitk=0):
        cap = data["values"].shape[1]
        valid = _batch_valid(cap, jnp.asarray(fills))
        slots = jnp.asarray(slots, jnp.int32)
        # single segment per window: the composite id IS the slot
        if table is not None:
            # full arena + num_cols: the width-1 selection happens after
            # the in-launch gather, never as an arena-wide slice copy
            if splitk > 0:
                out = segment_aggregate_block_table_splitk(
                    data["values"],
                    jnp.zeros((table.shape[0], cap), jnp.int32), table, 1,
                    splitk, valid=valid, slot_ids=slots,
                    num_slots=num_slots, stats=("sum", "count"),
                    mesh=mesh, num_cols=1)
            else:
                out = segment_aggregate_block_table(
                    data["values"],
                    jnp.zeros((table.shape[0], cap), jnp.int32), table, 1,
                    valid=valid, slot_ids=slots, num_slots=num_slots,
                    stats=("sum", "count"), mesh=mesh, num_cols=1)
        else:
            out = segment_aggregate_batched(
                jnp.asarray(data["values"][:, :, :1], jnp.float32),
                jnp.zeros((data["values"].shape[0], cap), jnp.int32), 1,
                valid=valid, slot_ids=slots,
                num_slots=num_slots, stats=("sum", "count"), mesh=mesh,
                splitk=splitk)
        return {"sum": out["sum"][:, 0, 0], "count": out["count"][:, 0]}

    def finalize_batch(acc, num_slots):
        s = np.asarray(acc["sum"])
        c = np.asarray(acc["count"])
        return [float(s[i] / max(c[i], 1.0)) for i in range(num_slots)]

    return WindowOperator("average", False, init_acc, fold, finalize,
                          fold_batch=fold_batch,
                          finalize_batch=finalize_batch,
                          supports_splitk=True)


# ------------------------------------------------------------------ bigrams

def _bigram_segment_count(ids, pval, slots, num_slots: int, vocab: int,
                          mesh) -> jnp.ndarray:
    """Composite (window_slot, pair) segment COUNT via one scatter —
    the big-vocab bigram path, where the one-hot matmul's
    [rows, num_slots * vocab^2] operand is memory-infeasible.

    ids [B, P] local pair ids (a * vocab + b), pval [B, P] pair validity,
    slots [B] window slots -> [num_slots, vocab^2] counts. With a mesh
    the scatter shards exactly like the dense kernel: rows arrive
    shard-major, each device rewrites its slots to shard-local indices
    and scatters into its own [slots_per * vocab^2] tile — psum-free
    (slots are disjoint), so sharded bigram batches genuinely
    distribute rather than silently falling back to one device.
    """
    v2 = vocab * vocab

    def flat_count(ids_, pv_, sl_, ns):
        total = ns * v2
        sid = (sl_.astype(jnp.int32)[:, None] * v2 + ids_).reshape(-1)
        sid = jnp.where(pv_.reshape(-1), sid, total)      # park invalid
        return jax.ops.segment_sum(
            pv_.reshape(-1).astype(jnp.float32), sid,
            num_segments=total + 1)[:total].reshape(ns, v2)

    if mesh is None or mesh.size <= 1:
        return flat_count(ids, pval, slots, num_slots)
    from jax.sharding import PartitionSpec as P
    axis = mesh.axis_names[0]
    num_devices = mesh.shape[axis]
    if ids.shape[0] % num_devices or num_slots % num_devices:
        # rows/slots that don't divide the mesh (callers outside the
        # executor's packed layout): correct unsharded fallback
        return flat_count(ids, pval, slots, num_slots)
    slots_per = num_slots // num_devices

    def shard_fn(ids_, pv_, sl_):
        base = jax.lax.axis_index(axis) * slots_per
        local = sl_.astype(jnp.int32) - base
        own = (local >= 0) & (local < slots_per)
        local = jnp.where(own, local, 0)
        return flat_count(ids_, pv_ & own[:, None], local, slots_per)

    f = jax.shard_map(shard_fn, mesh=mesh,
                      in_specs=(P(axis, None), P(axis, None), P(axis)),
                      out_specs=P(axis, None), check_vma=False)
    return f(ids, pval.astype(bool), slots)


def make_bigrams(block_capacity: int, width: int,
                 vocab: int = 256) -> WindowOperator:
    """Token payloads: each event's value row is a mini-document of
    ``width`` token ids; counts a dense [vocab, vocab] co-occurrence —
    deliberately compute-heavy like the paper's bigrams workload.

    Batch contract: every adjacent token pair is an "event" with the
    composite segment id ``(window_slot, a * vocab + b)`` and the bigram
    table is the per-slot segment COUNT — so bigrams ride the batched /
    pooled path through the same count-only kernel as the keyed
    operators (block-diagonal over slots: a pair only lands in its own
    window's [vocab, vocab] tile). The one-hot formulation materializes
    ``[rows, num_slots * vocab^2]``, which is only feasible for small
    vocab x slot products; above ``_BIGRAM_ONEHOT_LIMIT`` columns the
    fold switches to an equivalent one-launch ``segment_sum`` scatter
    (same composite ids, no one-hot temps).
    """
    from repro.kernels import segment_aggregate_batched

    _BIGRAM_ONEHOT_LIMIT = 8192

    def init_acc():
        return jnp.zeros((vocab, vocab), jnp.float32)

    @jax.jit
    def fold(acc, data, fill):
        toks = jnp.abs(data["values"]).astype(jnp.int32) % vocab  # [n, w]
        mask = _valid_mask(toks.shape[0], fill)
        onehot_a = jax.nn.one_hot(toks[:, :-1], vocab,
                                  dtype=jnp.float32)             # [n,w-1,V]
        # masking one side of the product suffices: an invalid row's
        # pairs contribute nothing anywhere (previously they collapsed
        # onto (0, 0) and were phantom-counted)
        onehot_a = onehot_a * mask[:, None, None]
        onehot_b = jax.nn.one_hot(toks[:, 1:], vocab, dtype=jnp.float32)
        contrib = jnp.einsum("nwa,nwb->ab", onehot_a, onehot_b)
        return acc + contrib

    def finalize(acc):
        return np.asarray(acc)

    @partial(jax.jit, static_argnames=("num_slots", "mesh", "splitk"))
    def fold_batch(data, fills, slots, num_slots, mesh=None, table=None,
                   splitk=0):
        # splitk deliberately ignored (supports_splitk=False): the
        # big-vocab scatter masks rows by slot ownership, so balanced or
        # chunk-padded rows would be silently dropped
        vals = data["values"]
        if table is not None:
            # pool gather: one take along the arena's pool axis (the
            # pair ids are derived values, so unlike the keyed folds the
            # tokens cannot be gathered in-kernel)
            vals = jnp.take(vals, table, axis=0)
        b, cap, w = vals.shape
        slots = jnp.asarray(slots, jnp.int32)
        if w < 2:
            return {"pairs": jnp.zeros((num_slots, vocab, vocab),
                                       jnp.float32)}
        toks = jnp.abs(vals).astype(jnp.int32) % vocab        # [B, cap, w]
        pair = toks[:, :, :-1] * vocab + toks[:, :, 1:]       # [B, cap, w-1]
        valid = _batch_valid(cap, jnp.asarray(fills))         # [B, cap]
        pvalid = jnp.broadcast_to(valid[:, :, None], pair.shape)
        ids = pair.reshape(b, cap * (w - 1))
        pval = pvalid.reshape(b, cap * (w - 1))
        if num_slots * vocab * vocab <= _BIGRAM_ONEHOT_LIMIT:
            ones = jnp.ones((b, cap * (w - 1), 1), jnp.float32)
            out = segment_aggregate_batched(
                ones, ids, vocab * vocab, valid=pval, slot_ids=slots,
                num_slots=num_slots, stats=("count",), mesh=mesh)
            cnt = out["count"]
        else:
            cnt = _bigram_segment_count(ids, pval, slots, num_slots,
                                        vocab, mesh)
        return {"pairs": cnt.reshape(num_slots, vocab, vocab)}

    def finalize_batch(acc, num_slots):
        pairs = np.asarray(acc["pairs"])
        return [pairs[i] for i in range(num_slots)]

    return WindowOperator("bigrams", False, init_acc, fold, finalize,
                          fold_batch=fold_batch,
                          finalize_batch=finalize_batch)


# -------------------------------------------------------------------- stock

def make_stock(block_capacity: int, width: int,
               num_keys: int = 128,
               use_kernel: bool = False) -> WindowOperator:
    """Rolling per-symbol aggregates + price-warning alerts (>=5% swing).

    ``use_kernel=True`` folds each block through the ``segment_aggregate``
    Pallas kernel (interpret-mode on CPU, Mosaic on TPU) instead of the
    jnp scatter path — the engine hot loop on the MXU."""

    def init_acc():
        return {
            "min": jnp.full((num_keys,), jnp.inf, jnp.float32),
            "max": jnp.full((num_keys,), -jnp.inf, jnp.float32),
            "sum": jnp.zeros((num_keys,), jnp.float32),
            "count": jnp.zeros((num_keys,), jnp.float32),
        }

    if use_kernel:
        from repro.kernels import segment_aggregate

        @jax.jit
        def fold(acc, data, fill):
            n = data["values"].shape[0]
            mask = _valid_mask(n, fill)
            keys = jnp.asarray(data["keys"], jnp.int32) % num_keys
            out = segment_aggregate(
                jnp.asarray(data["values"][:, :1], jnp.float32), keys,
                num_keys, valid=mask)
            return {
                "min": jnp.minimum(acc["min"], out["min"][:, 0]),
                "max": jnp.maximum(acc["max"], out["max"][:, 0]),
                "sum": acc["sum"] + out["sum"][:, 0],
                "count": acc["count"] + out["count"],
            }
    else:
        @jax.jit
        def fold(acc, data, fill):
            n = data["values"].shape[0]
            mask = _valid_mask(n, fill)
            keys = jnp.where(mask, data["keys"], 0) % num_keys
            price = data["values"][:, 0]
            big = jnp.where(mask, price, -jnp.inf)
            small = jnp.where(mask, price, jnp.inf)
            return {
                "min": acc["min"].at[keys].min(jnp.where(mask, small, jnp.inf)),
                "max": acc["max"].at[keys].max(jnp.where(mask, big, -jnp.inf)),
                "sum": acc["sum"].at[keys].add(jnp.where(mask, price, 0.0)),
                "count": acc["count"].at[keys].add(mask.astype(jnp.float32)),
            }

    def finalize(acc):
        mean = np.asarray(acc["sum"] / jnp.maximum(acc["count"], 1.0))
        mx, mn = np.asarray(acc["max"]), np.asarray(acc["min"])
        with np.errstate(invalid="ignore"):
            alerts = (mx - mn) / np.where(mn > 0, mn, np.inf) >= 0.05
        return {"mean": mean, "min": mn, "max": mx, "alerts": alerts}

    from repro.kernels import (
        segment_aggregate_batched, segment_aggregate_block_table,
        segment_aggregate_block_table_splitk,
    )

    @partial(jax.jit, static_argnames=("num_slots", "mesh", "splitk"))
    def fold_batch(data, fills, slots, num_slots, mesh=None, table=None,
                   splitk=0):
        cap = data["values"].shape[1]
        valid = _batch_valid(cap, jnp.asarray(fills))
        slots = jnp.asarray(slots, jnp.int32)
        if table is not None:
            # keys gather cheaply via one take (int32, needed to derive
            # segment ids); the fat value tiles stay in the arena and are
            # gathered inside the kernel launch (num_cols selects the
            # price column post-gather — no arena-wide slice copy)
            keys = jnp.take(jnp.asarray(data["keys"], jnp.int32), table,
                            axis=0) % num_keys
            if splitk > 0:
                out = segment_aggregate_block_table_splitk(
                    data["values"], keys, table, num_keys, splitk,
                    valid=valid, slot_ids=slots, num_slots=num_slots,
                    mesh=mesh, num_cols=1)
            else:
                out = segment_aggregate_block_table(
                    data["values"], keys,
                    table, num_keys, valid=valid, slot_ids=slots,
                    num_slots=num_slots, mesh=mesh, num_cols=1)
        else:
            keys = jnp.asarray(data["keys"], jnp.int32) % num_keys
            out = segment_aggregate_batched(
                jnp.asarray(data["values"][:, :, :1], jnp.float32), keys,
                num_keys, valid=valid, slot_ids=slots,
                num_slots=num_slots, mesh=mesh, splitk=splitk)
        return {"min": out["min"][:, :, 0], "max": out["max"][:, :, 0],
                "sum": out["sum"][:, :, 0], "count": out["count"]}

    return WindowOperator("stock", False, init_acc, fold, finalize,
                          fold_batch=fold_batch,
                          finalize_batch=_per_slot_finalize(finalize),
                          supports_splitk=True)


# ---------------------------------------------------------------------- lrb

def make_lrb(block_capacity: int, width: int,
             num_segments: int = 256) -> WindowOperator:
    """Linear Road: values[:,0]=speed, values[:,1]=lane; per-segment vehicle
    count + average speed + accident flag (stopped vehicles) -> toll."""

    def init_acc():
        return {
            "count": jnp.zeros((num_segments,), jnp.float32),
            "speed_sum": jnp.zeros((num_segments,), jnp.float32),
            "stopped": jnp.zeros((num_segments,), jnp.float32),
        }

    @jax.jit
    def fold(acc, data, fill):
        n = data["values"].shape[0]
        mask = _valid_mask(n, fill)
        seg = jnp.where(mask, data["keys"], 0) % num_segments
        speed = data["values"][:, 0]
        stopped = mask & (speed <= 1e-3)
        return {
            "count": acc["count"].at[seg].add(mask.astype(jnp.float32)),
            "speed_sum": acc["speed_sum"].at[seg].add(
                jnp.where(mask, speed, 0.0)),
            "stopped": acc["stopped"].at[seg].add(stopped.astype(jnp.float32)),
        }

    def finalize(acc):
        count = np.asarray(acc["count"])
        avg_speed = np.asarray(acc["speed_sum"]) / np.maximum(count, 1.0)
        accident = np.asarray(acc["stopped"]) >= 2
        base = 2.0
        congestion = np.maximum(count - 50, 0.0)
        toll = np.where(accident, 0.0, base * congestion ** 2 * 1e-4)
        return {"count": count, "avg_speed": avg_speed,
                "accident": accident, "toll": toll}

    from repro.kernels import segment_aggregate_batched

    @partial(jax.jit, static_argnames=("num_slots", "mesh", "splitk"))
    def fold_batch(data, fills, slots, num_slots, mesh=None, table=None,
                   splitk=0):
        keys, values = data["keys"], data["values"]
        if table is not None:
            # the fold consumes DERIVED values ([speed, stopped]), so the
            # pool gather is one take along the arena's pool axis per
            # tensor — still a single fused gather op, not O(rows)
            # concats. splitk chunking therefore happens at the executor
            # (chunk-group launches merged via merge_acc) rather than
            # inside this launch; the stacked sharded fold below still
            # honours the balanced split-K layout.
            keys = jnp.take(jnp.asarray(keys, jnp.int32), table, axis=0)
            values = jnp.take(values, table, axis=0)
        cap = values.shape[1]
        valid = _batch_valid(cap, jnp.asarray(fills))
        seg = jnp.asarray(keys, jnp.int32) % num_segments
        speed = jnp.asarray(values[:, :, 0], jnp.float32)
        stopped = (valid & (speed <= 1e-3)).astype(jnp.float32)
        # width-2 payload: the segment-sum of [speed, stopped] yields both
        # speed_sum and the stopped-vehicle count in one kernel pass
        vals = jnp.stack([speed, stopped], axis=-1)
        out = segment_aggregate_batched(
            vals, seg, num_segments, valid=valid,
            slot_ids=jnp.asarray(slots, jnp.int32), num_slots=num_slots,
            stats=("sum", "count"), mesh=mesh, splitk=splitk)
        return {"count": out["count"], "speed_sum": out["sum"][:, :, 0],
                "stopped": out["sum"][:, :, 1]}

    return WindowOperator("lrb", False, init_acc, fold, finalize,
                          fold_batch=fold_batch,
                          finalize_batch=_per_slot_finalize(finalize),
                          supports_splitk=True)


# --------------------------------------------------------------- percentile

def make_percentile(block_capacity: int, width: int,
                    qs=(0.5, 0.95, 0.99)) -> WindowOperator:
    """BLOCKING operator (paper §3.3): the full window must be resident
    before the percentiles can be computed.

    Batch contract (PR 8, the last per-window straggler): the per-slot
    accumulator is a NaN-padded **sorted run** of the slot's valid values
    (``jnp.sort`` orders NaN last, so the first ``count`` entries are the
    ascending data) — exact, not a sketch. Two accumulators merge by
    concatenating runs and re-sorting (a sorted-merge), which is why the
    ``merge`` override exists: the default add-merge would corrupt the
    state. The merge composes with the split-K executor's chunk-group
    partials; ``mesh``/``splitk`` are otherwise ignored inside the fold
    (a sort has no per-slot reduction to shard)."""

    def init_acc():
        return []

    def fold(acc, data, fill):
        # blocking: accumulate device blocks; compute happens in finalize
        acc.append((data["values"][:, 0], fill))
        return acc

    def finalize(acc):
        if not acc:
            return {q: float("nan") for q in qs}
        vals = jnp.concatenate([
            jnp.where(_valid_mask(v.shape[0], f), v, jnp.nan)
            for v, f in acc])
        vals = vals[~jnp.isnan(vals)]
        return {q: float(jnp.quantile(vals, q)) for q in qs}

    @partial(jax.jit, static_argnames=("num_slots", "mesh", "splitk"))
    def fold_batch(data, fills, slots, num_slots, mesh=None, table=None,
                   splitk=0):
        vals = data["values"]
        if table is not None:
            # pool gather: one take along the arena's pool axis (the
            # sort consumes every row's values, so there is no in-kernel
            # formulation to route through)
            vals = jnp.take(vals, table, axis=0)
        v = jnp.asarray(vals[:, :, 0], jnp.float32)           # [B, cap]
        b, cap = v.shape
        valid = _batch_valid(cap, jnp.asarray(fills))
        sl = jnp.asarray(slots, jnp.int32)
        keep = valid[:, :, None] & (sl[:, None, None] ==
                                    jnp.arange(num_slots)[None, None, :])
        mat = jnp.where(keep, v[:, :, None], jnp.nan) \
            .transpose(2, 0, 1).reshape(num_slots, b * cap)
        return {"sorted": jnp.sort(mat, axis=1),
                "count": jnp.sum(keep, axis=(0, 1)).astype(jnp.int32)}

    def merge(a, b):
        # sorted-merge: concatenate the runs and re-sort (NaN padding
        # stays at the tail); counts add
        return {"sorted": jnp.sort(jnp.concatenate(
                    [a["sorted"], b["sorted"]], axis=1), axis=1),
                "count": a["count"] + b["count"]}

    def finalize_batch(acc, num_slots):
        srt = np.asarray(acc["sorted"])
        cnt = np.asarray(acc["count"])
        out = []
        for i in range(num_slots):
            n = int(cnt[i])
            if n == 0:
                out.append({q: float("nan") for q in qs})
            else:
                out.append({q: float(np.quantile(srt[i, :n], q))
                            for q in qs})
        return out

    return WindowOperator("percentile", True, init_acc, fold, finalize,
                          fold_batch=fold_batch,
                          finalize_batch=finalize_batch,
                          merge=merge, supports_splitk=True)


OPERATORS = {
    "average": make_average,
    "bigrams": make_bigrams,
    "stock": make_stock,
    "lrb": make_lrb,
    "percentile": make_percentile,
}


def make_operator(name: str, block_capacity: int, width: int,
                  **kw) -> WindowOperator:
    if name not in OPERATORS:
        raise KeyError(f"unknown operator {name!r}")
    return OPERATORS[name](block_capacity, width, **kw)
