"""Pallas TPU kernel: windowed segment aggregation (reduce-by-key).

The streaming engine's hot loop: fold a block batch of events into
per-key aggregates (sum / count / min / max). TPU adaptation: scatter-by-
key is hostile to the VPU, so the kernel converts the segment reduction
into **one-hot matmuls on the MXU** — ``onehot(ids)^T @ values`` — which is
the TPU-native formulation of reduce-by-key (FeatGraph/GE-SpMM style).

Tiling: grid over event tiles of ``n`` events; each step loads a
``[W, n]`` value tile (events on the lanes) and a ``[1, n]`` row of
segment ids (``-1`` = invalid) into VMEM, builds the one-hot ``[n, S]``
in chunks of at most 512 segments, and accumulates lane-dense ``[W, S]``
/ ``[1, S]`` outputs that stay resident in VMEM across the whole grid
(output BlockSpecs map every step to the same block): sum on the MXU at
``HIGHEST`` precision, count and min/max as 2-D masked reductions. No
block breaks the (8, 128) tiling, and events-on-lanes is the TPU's own
layout for a ``[P, cap, W]`` arena whose width is not a lane multiple,
so the block-table folds read the arena without relaying it out.

The **batched** entry point (``segment_aggregate_batched_pallas``) extends
this to many concurrent windows in one device pass: event rows carry a
2-D segment layout ``(window_slot, key)`` which is flattened into the
segment axis (``sid = slot * S + key``) so a single kernel launch reduces
every due window at once — the engine's multi-window execution path.

The **sharded** entry point (``segment_aggregate_batched_sharded``)
partitions that composite segment axis across a 1-D device mesh: device
``d`` owns the contiguous slot range ``[d*slots_per, (d+1)*slots_per)``
and reduces only the block rows placed in its shard. Slots are disjoint,
so shards never touch each other's outputs and the gather needs **no
cross-device reduction** (no psum) — the output is simply each shard's
``[slots_per, S, ...]`` tile concatenated along the slot axis. Rows must
arrive in shard-major order (``pack_rows_shard_major``); a row whose slot
falls outside its shard's range is defensively masked invalid rather than
corrupting a neighbour's slot.

The **block-table** entry points (``segment_aggregate_block_table_*``)
are the zero-copy gather path over the persistent device block pool
(``core.block_pool``): instead of stacked ``[R, cap, W]`` event tensors
they take the whole ``[pool_slots, cap, W]`` values arena plus a ``[R]``
table of pool-slot indices, and gather each row's event tile from the
arena *inside* the launch — a scalar-prefetched ``index_map`` dereference
on the Mosaic path (the flash-decoding ``block_tables`` idiom, one DMA
per row straight out of the arena), a single ``jnp.take`` along the pool
axis on the dense path. The sharded variant partitions BOTH the arena
and the table over the mesh, so each device gathers only from its own
``[pool_slots/D, ...]`` arena tile — the table stays shard-local.

The **split-K** entry points (``segment_aggregate_block_table_splitk_*``)
are the second half of the flash-decoding idiom: the table's row axis is
partitioned into ``k`` fixed-shape chunks of ``chunk_rows`` rows, each
chunk's grid programs fold into their own ``mid_o``-style partial
accumulator (leading chunk axis on every out_shape), and the partials
merge through each stat's own identity (sum/count add, min/max
elementwise extrema — ``merge_partials``). Because every launch shape is
``chunk_rows`` regardless of batch size, varying batches reuse one
compiled kernel instead of recompiling per power-of-two bucket, and a
skewed window whose rows dominate the batch folds across chunks in
parallel instead of serializing one segment stripe.
``segment_aggregate_batched_splitk_sharded`` is the distributed form:
rows balance across the mesh ignoring slot ownership, each device folds
a FULL per-slot partial, and the per-device partials merge after the
``shard_map``.

All Pallas entry points thread ``stats`` through their ``out_shape``s:
sum/count-only folds (average, lrb) never allocate or compute the
min/max VPU broadcast-reduce, matching the dense backend.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

ALL_STATS = ("sum", "count", "min", "max")


def norm_stats(stats) -> Tuple[str, ...]:
    """Canonicalize a stats selection: fixed order, validated, deduped —
    so jit caches don't fork on permutations of the same request."""
    stats = tuple(stats)
    for s in stats:
        if s not in ALL_STATS:
            raise ValueError(f"unknown stat {s!r} (of {ALL_STATS})")
    out = tuple(s for s in ALL_STATS if s in stats)
    if not out:
        raise ValueError("stats selection is empty")
    return out


# one-hot tile width along the segment axis: bounds the [n, S] one-hot
# and its masked min/max temporaries to [n, 512] whatever the segment count
_SEG_CHUNK = 512


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _acc_tile(refs, ids, vals, num_segments: int) -> None:
    """Accumulate one event tile into the stat refs.

    ``ids`` is a ``[1, n]`` row of segment ids (``-1`` marks an invalid
    event) and ``vals`` the ``[W, n]`` values with events on the lanes —
    the orientation of an arena tile in the TPU's layout for it. The
    accumulators are lane-dense: ``[W, S]`` for sum/min/max, ``[1, S]``
    for count. Shared by the flat-grid kernel and the block-table
    kernels. Only the requested stats exist in ``refs``; unrequested
    aggregates cost nothing."""
    n = ids.shape[1]
    w = vals.shape[0]
    col = ids.T                                           # [n, 1]
    if "sum" in refs:
        # an invalid event contributes nothing even if its values are
        # not finite (0 * inf would poison the matmul)
        vals_ok = jnp.where(ids >= 0, vals, 0.0)
    for s0 in range(0, num_segments, _SEG_CHUNK):
        c = min(_SEG_CHUNK, num_segments - s0)
        seg = jax.lax.broadcasted_iota(jnp.int32, (n, c), 1) + s0
        onehot = col == seg                               # [n, c]
        if "sum" in refs or "count" in refs:
            oh_f = onehot.astype(jnp.float32)
        if "sum" in refs:
            # MXU path: [W, n] @ [n, c]
            refs["sum"][:, s0:s0 + c] += jnp.dot(
                vals_ok, oh_f, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
        if "count" in refs:
            refs["count"][:, s0:s0 + c] += jnp.sum(oh_f, axis=0,
                                                   keepdims=True)
        # min/max: one masked [n, c] reduction per value column (VPU)
        for j in range(w if ("min" in refs or "max" in refs) else 0):
            v = vals[j:j + 1, :].T                        # [n, 1]
            if "min" in refs:
                lo = jnp.min(jnp.where(onehot, v, jnp.inf), axis=0,
                             keepdims=True)
                refs["min"][j:j + 1, s0:s0 + c] = jnp.minimum(
                    refs["min"][j:j + 1, s0:s0 + c], lo)
            if "max" in refs:
                hi = jnp.max(jnp.where(onehot, v, -jnp.inf), axis=0,
                             keepdims=True)
                refs["max"][j:j + 1, s0:s0 + c] = jnp.maximum(
                    refs["max"][j:j + 1, s0:s0 + c], hi)


def _init_refs(refs) -> None:
    for name, ref in refs.items():
        if name == "min":
            ref[...] = jnp.full_like(ref, jnp.inf)
        elif name == "max":
            ref[...] = jnp.full_like(ref, -jnp.inf)
        else:
            ref[...] = jnp.zeros_like(ref)


def _kernel(ids_ref, values_ref, *out_refs, num_segments: int,
            stats: Tuple[str, ...]):
    refs = dict(zip(stats, out_refs))

    @pl.when(pl.program_id(0) == 0)
    def _init():
        _init_refs(refs)

    _acc_tile(refs, ids_ref[...], values_ref[...], num_segments)


def _stat_outputs(stats: Tuple[str, ...], num_segments: int, w: int,
                  k: Optional[int] = None):
    """(out_shapes, out_specs) for a stats selection, in the kernel's
    lane-dense layout (``[W, S]``, count ``[1, S]``). Every grid step maps
    to the same block so accumulators stay VMEM-resident (the variadic
    index_maps absorb grid indices and any scalar-prefetch operands).

    ``k`` adds the split-K leading chunk axis ``[k, ...]``: chunk ``c``'s
    programs all map to block ``c``, so each chunk's partial accumulator
    stays resident across its inner steps (the grid iterates the row
    axis fastest) and is re-initialized when the next chunk begins."""
    shapes = []
    specs = []
    for s in stats:
        rows = 1 if s == "count" else w
        if k is None:
            shapes.append(jax.ShapeDtypeStruct((rows, num_segments),
                                               jnp.float32))
            specs.append(pl.BlockSpec((rows, num_segments),
                                      lambda *a: (0, 0)))
        else:
            shapes.append(jax.ShapeDtypeStruct((k, rows, num_segments),
                                               jnp.float32))
            specs.append(pl.BlockSpec((None, rows, num_segments),
                                      lambda c, *a: (c, 0, 0)))
    return tuple(shapes), tuple(specs)


def _from_kernel_layout(out: dict, lead: Tuple[int, ...], num_slots: int,
                        num_segments: int) -> dict:
    """Kernel outputs (``[*lead, W, S]``, count ``[*lead, 1, S]``) back to
    the public layout ``[*lead, num_slots, num_segments(, W)]``."""
    shaped = {}
    for s, v in out.items():
        if s == "count":
            shaped[s] = v.reshape(*lead, num_slots, num_segments)
        else:
            v = jnp.swapaxes(v, -1, -2)
            shaped[s] = v.reshape(*lead, num_slots, num_segments,
                                  v.shape[-1])
    return shaped


def _masked_ids(segment_ids, valid):
    """Segment ids with invalid events folded in as ``-1`` (the kernels
    match ids against ``[0, S)``, so ``-1`` lands in no segment)."""
    ids = segment_ids.astype(jnp.int32)
    if valid is None:
        return ids
    return jnp.where(valid.astype(bool), ids, -1)


def segment_aggregate_pallas(values: jnp.ndarray, segment_ids: jnp.ndarray,
                             num_segments: int,
                             valid: Optional[jnp.ndarray] = None,
                             block_n: int = 512,
                             interpret: bool = True,
                             stats: Tuple[str, ...] = ALL_STATS):
    """values [N, W] f32, segment_ids [N] i32 -> dict of [S, W]/[S] aggs.

    N is padded to a multiple of ``block_n`` (itself a multiple of the
    128-lane tile); padding rows are invalid. The kernel reads ids as a
    ``[1, N]`` row and values transposed to ``[W, N]``, so every block is
    lane-dense. ``stats`` selects which aggregates the kernel
    materializes (threaded through ``out_shape`` — unrequested stats are
    never computed).
    """
    stats = norm_stats(stats)
    n, w = values.shape
    ids = _masked_ids(segment_ids, valid)
    block_n = min(_round_up(block_n, 128), _round_up(max(n, 1), 128))
    pad = (-n) % block_n
    vals_t = values.astype(jnp.float32).T                  # [W, N]
    if pad:
        vals_t = jnp.pad(vals_t, ((0, 0), (0, pad)))
        ids = jnp.pad(ids, (0, pad), constant_values=-1)
    n_pad = n + pad

    kernel = functools.partial(_kernel, num_segments=num_segments,
                               stats=stats)
    out_shapes, out_specs = _stat_outputs(stats, num_segments, w)
    outs = pl.pallas_call(
        kernel,
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((1, block_n), lambda i: (0, i)),
            pl.BlockSpec((w, block_n), lambda i: (0, i)),
        ],
        out_specs=out_specs,
        out_shape=out_shapes,
        interpret=interpret,
    )(ids.reshape(1, n_pad), vals_t)
    return {s: (o[0] if s == "count" else o.T) for s, o in zip(stats, outs)}


def segment_aggregate_batched_pallas(values: jnp.ndarray,
                                     segment_ids: jnp.ndarray,
                                     num_segments: int,
                                     valid: Optional[jnp.ndarray] = None,
                                     slot_ids: Optional[jnp.ndarray] = None,
                                     num_slots: Optional[int] = None,
                                     block_n: int = 512,
                                     interpret: bool = True,
                                     stats: Tuple[str, ...] = ALL_STATS):
    """Multi-window segment aggregation in ONE kernel launch.

    values [B, N, W] f32, segment_ids [B, N] i32 -> per-slot aggregates
    {sum [num_slots, S, W], count [num_slots, S], min, max} — restricted
    to the requested ``stats`` (threaded through the kernel out_shapes,
    so sum/count-only folds skip the min/max VPU work entirely).

    Each of the B rows is a padded event block (``valid`` masks ragged
    fills); ``slot_ids [B]`` maps rows to output window slots, so several
    blocks of the same window may share a slot (default: ``arange(B)``,
    one row per slot). The 2-D segment layout ``(slot, key)`` is flattened
    into the segment axis — ``sid = slot * num_segments + key`` — and fed
    through the same one-hot-matmul grid as the single-window kernel.
    """
    stats = norm_stats(stats)
    b, n, w = values.shape
    if slot_ids is None:
        slot_ids = jnp.arange(b, dtype=jnp.int32)
        if num_slots is None:
            num_slots = b
    elif num_slots is None:
        raise ValueError("num_slots is required when slot_ids is given")
    composite = (slot_ids.astype(jnp.int32)[:, None] * num_segments
                 + segment_ids.astype(jnp.int32))        # [B, N]
    out = segment_aggregate_pallas(
        values.reshape(b * n, w), composite.reshape(b * n),
        num_slots * num_segments,
        valid=None if valid is None else valid.reshape(b * n),
        block_n=block_n, interpret=interpret, stats=stats)
    shaped = {}
    for s in stats:
        if s == "count":
            shaped[s] = out[s].reshape(num_slots, num_segments)
        else:
            shaped[s] = out[s].reshape(num_slots, num_segments, w)
    return shaped


def segment_aggregate_batched_dense(values: jnp.ndarray,
                                    segment_ids: jnp.ndarray,
                                    num_segments: int,
                                    valid: Optional[jnp.ndarray] = None,
                                    slot_ids: Optional[jnp.ndarray] = None,
                                    num_slots: Optional[int] = None,
                                    stats: Tuple[str, ...] = (
                                        "sum", "count", "min", "max")):
    """The kernel's one-hot formulation as plain jnp — the non-TPU hot
    path for the batched engine fold.

    Same contract as ``segment_aggregate_batched_pallas``. XLA:CPU lowers
    ``jax.ops.segment_*`` to serial scatters, which is orders slower than
    the one-hot matmul this uses (identical math to the Mosaic kernel);
    ``stats`` lets callers skip the min/max broadcast-reduce temps when
    only sum/count are needed (the average and LRB folds).
    """
    b, n, w = values.shape
    if valid is None:
        valid = jnp.ones((b, n), bool)
    if slot_ids is None:
        slot_ids = jnp.arange(b, dtype=jnp.int32)
        if num_slots is None:
            num_slots = b
    elif num_slots is None:
        raise ValueError("num_slots is required when slot_ids is given")
    s_total = num_slots * num_segments
    composite = (slot_ids.astype(jnp.int32)[:, None] * num_segments
                 + segment_ids.astype(jnp.int32)).reshape(b * n)
    flat_valid = valid.reshape(b * n).astype(bool)
    flat_vals = values.reshape(b * n, w).astype(jnp.float32)
    onehot = (composite[:, None] ==
              jnp.arange(s_total, dtype=jnp.int32)[None, :]) \
        & flat_valid[:, None]                               # [B*N, S]
    oh_f = onehot.astype(jnp.float32)
    out = {}
    if "sum" in stats:
        out["sum"] = jax.lax.dot_general(
            oh_f, jnp.where(flat_valid[:, None], flat_vals, 0.0),
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ).reshape(num_slots, num_segments, w)
    if "count" in stats:
        out["count"] = jnp.sum(oh_f, axis=0).reshape(num_slots,
                                                     num_segments)
    if "min" in stats:
        big = jnp.where(onehot[:, :, None], flat_vals[:, None, :], jnp.inf)
        out["min"] = jnp.min(big, axis=0).reshape(num_slots, num_segments,
                                                  w)
    if "max" in stats:
        small = jnp.where(onehot[:, :, None], flat_vals[:, None, :],
                          -jnp.inf)
        out["max"] = jnp.max(small, axis=0).reshape(num_slots,
                                                    num_segments, w)
    return out


def _bt_kernel(table_ref, ids_ref, arena_ref, *out_refs,
               num_segments: int, stats: Tuple[str, ...],
               num_cols: Optional[int], row_axis: int):
    """Block-table kernel body: one grid step per table row. The arena
    BlockSpec's index_map dereferences the scalar-prefetched table, so
    each step DMAs its event tile straight out of the pool arena — the
    row gather happens inside the launch, not as a host/device concat.
    ``num_cols`` selects a value-row prefix of the ``[W, cap]`` tile (the
    DMA already skips all but the first 8-row group). ``row_axis`` is the
    grid axis that walks rows: accumulators re-init when it restarts (0
    for the plain fold; 1 for split-K, whose axis 0 walks the chunks)."""
    refs = dict(zip(stats, out_refs))

    @pl.when(pl.program_id(row_axis) == 0)
    def _init():
        _init_refs(refs)

    vals = arena_ref[...]                                 # [W', cap]
    if num_cols is not None and num_cols < vals.shape[0]:
        vals = vals[:num_cols]
    _acc_tile(refs, ids_ref[...], vals, num_segments)


def _block_table_call(values_arena, composite, table, num_slots: int,
                      num_segments: int, stats: Tuple[str, ...],
                      num_cols: Optional[int], interpret: bool,
                      chunk_rows: Optional[int] = None) -> dict:
    """Launch the block-table kernel over ``composite`` ids ``[R, cap]``
    (invalid events already ``-1``) and return the public layout.

    The kernel reads the arena as ``[P, W, cap]`` tiles, events on the
    lanes. That is the TPU's own layout for a ``[P, cap, W]`` arena whose
    width is not a multiple of 128 lanes (the Table-1 widths 416 and 576),
    so the transpose below costs no relayout of the arena.
    ``chunk_rows`` selects the split-K grid ``(k, chunk_rows)`` with one
    partial accumulator per chunk (leading ``k`` axis on the outputs)."""
    p, cap, w = values_arena.shape
    w_out = num_cols if num_cols is not None else w
    w_blk = w if num_cols is None else min(w, _round_up(num_cols, 8))
    r = table.shape[0]
    s_total = num_slots * num_segments
    arena_t = jnp.swapaxes(values_arena.astype(jnp.float32), 1, 2)
    if chunk_rows is None:
        k = None
        grid = (r,)
        in_specs = [
            pl.BlockSpec((None, 1, cap), lambda i, tbl: (i, 0, 0)),
            pl.BlockSpec((None, w_blk, cap),
                         lambda i, tbl: (tbl[i], 0, 0)),
        ]
    else:
        k = r // chunk_rows
        grid = (k, chunk_rows)
        in_specs = [
            pl.BlockSpec((None, 1, cap),
                         lambda c, i, tbl: (c * chunk_rows + i, 0, 0)),
            pl.BlockSpec((None, w_blk, cap),
                         lambda c, i, tbl: (tbl[c * chunk_rows + i], 0, 0)),
        ]
    out_shapes, out_specs = _stat_outputs(stats, s_total, w_out, k)
    kernel = functools.partial(_bt_kernel, num_segments=s_total,
                               stats=stats, num_cols=num_cols,
                               row_axis=0 if k is None else 1)
    outs = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shapes,
        interpret=interpret,
    )(table.astype(jnp.int32), composite.reshape(r, 1, cap), arena_t)
    return _from_kernel_layout(dict(zip(stats, outs)),
                               () if k is None else (k,), num_slots,
                               num_segments)


def segment_aggregate_block_table_pallas(
        values_arena: jnp.ndarray, segment_ids: jnp.ndarray,
        table: jnp.ndarray, num_segments: int,
        valid: Optional[jnp.ndarray] = None,
        slot_ids: Optional[jnp.ndarray] = None,
        num_slots: Optional[int] = None,
        interpret: bool = True,
        stats: Tuple[str, ...] = ALL_STATS,
        num_cols: Optional[int] = None):
    """Batched fold over a persistent block pool, gathering in-kernel.

    values_arena [pool_slots, cap, W] f32 (the device arena), table [R]
    i32 pool-slot indices, segment_ids [R, cap] i32, slot_ids [R] window
    slots -> per-slot aggregates as ``segment_aggregate_batched_pallas``.
    The table is a scalar-prefetch operand: grid step ``r`` DMAs arena
    row ``table[r]`` into VMEM (flash-decoding's ``block_tables`` idiom),
    so already-resident blocks are folded with zero per-batch copies.
    ``num_cols`` restricts the fold to the leading value columns, sliced
    per-tile inside the kernel (width-selecting operators pass the FULL
    arena — never an arena-wide slice copy).
    """
    stats = norm_stats(stats)
    r = table.shape[0]
    if slot_ids is None:
        slot_ids = jnp.arange(r, dtype=jnp.int32)
        if num_slots is None:
            num_slots = r
    elif num_slots is None:
        raise ValueError("num_slots is required when slot_ids is given")
    composite = _masked_ids(slot_ids.astype(jnp.int32)[:, None]
                            * num_segments + segment_ids, valid)
    return _block_table_call(values_arena, composite, table, num_slots,
                             num_segments, stats, num_cols, interpret)


def segment_aggregate_block_table_dense(
        values_arena: jnp.ndarray, segment_ids: jnp.ndarray,
        table: jnp.ndarray, num_segments: int,
        valid: Optional[jnp.ndarray] = None,
        slot_ids: Optional[jnp.ndarray] = None,
        num_slots: Optional[int] = None,
        stats: Tuple[str, ...] = ALL_STATS,
        num_cols: Optional[int] = None):
    """Dense-backend block-table fold: ONE ``jnp.take`` along the pool
    axis materializes the batch (a single device gather op, replacing the
    O(rows) per-row concat of the stacked path), then the one-hot fold.
    ``num_cols`` slices the value columns AFTER the gather — O(rows),
    never an arena-wide copy.
    """
    vals = jnp.take(values_arena, table.astype(jnp.int32), axis=0)
    if num_cols is not None:
        vals = vals[:, :, :num_cols]
    return segment_aggregate_batched_dense(
        vals, segment_ids, num_segments, valid=valid, slot_ids=slot_ids,
        num_slots=num_slots, stats=norm_stats(stats))


def merge_partials(partials: dict) -> dict:
    """Merge ``[k, ...]`` per-chunk partial accumulators along the leading
    chunk axis through each stat's identity: sum/count add, min/max take
    elementwise extrema. ``k == 0`` (an empty chunk set) merges to the
    fold identity — a degenerate ``jnp.min`` over an empty axis would
    raise, and the identity is what an empty batch must produce."""
    out = {}
    for s, v in partials.items():
        if v.shape[0] == 0:
            if s == "min":
                out[s] = jnp.full(v.shape[1:], jnp.inf)
            elif s == "max":
                out[s] = jnp.full(v.shape[1:], -jnp.inf)
            else:
                out[s] = jnp.zeros(v.shape[1:], jnp.float32)
        elif s == "min":
            out[s] = jnp.min(v, axis=0)
        elif s == "max":
            out[s] = jnp.max(v, axis=0)
        else:
            out[s] = jnp.sum(v, axis=0)
    return out


def _splitk_empty(stats, num_slots, num_segments, w_out, merge):
    """Zero-row result for the split-K entry points: the fold identity
    when merging, else a genuinely empty ``k == 0`` partial stack."""
    empty = empty_batch_identity(num_slots, num_segments, w_out)
    merged = {s: empty[s] for s in stats}
    if merge:
        return merged
    return {s: v[None][:0] for s, v in merged.items()}


def segment_aggregate_block_table_splitk_pallas(
        values_arena: jnp.ndarray, segment_ids: jnp.ndarray,
        table: jnp.ndarray, num_segments: int, chunk_rows: int,
        valid: Optional[jnp.ndarray] = None,
        slot_ids: Optional[jnp.ndarray] = None,
        num_slots: Optional[int] = None,
        interpret: bool = True,
        stats: Tuple[str, ...] = ALL_STATS,
        num_cols: Optional[int] = None,
        merge: bool = True):
    """Split-K block-table fold: fixed-shape chunked partial accumulators.

    Same gather contract as ``segment_aggregate_block_table_pallas``, but
    the ``R`` table rows are padded to a multiple of ``chunk_rows`` and
    folded by a ``(k, chunk_rows)`` grid where chunk ``c`` accumulates
    rows ``[c*chunk_rows, (c+1)*chunk_rows)`` into its own partial out
    block (the exemplar's ``mid_o``). Padding rows are fully invalid
    (table entry 0, slot 0, valid 0) so they contribute nothing to any
    chunk's partial — including min/max, whose identities are ±inf, not
    zero. ``merge=False`` returns the raw ``[k, num_slots, S(, W)]``
    partials for caller-side (cross-launch) merging; the default merges
    on device via ``merge_partials``.
    """
    stats = norm_stats(stats)
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    p, cap, w = values_arena.shape
    w_out = num_cols if num_cols is not None else w
    r = table.shape[0]
    if slot_ids is None:
        slot_ids = jnp.arange(r, dtype=jnp.int32)
        if num_slots is None:
            num_slots = r
    elif num_slots is None:
        raise ValueError("num_slots is required when slot_ids is given")
    if r == 0 or num_slots == 0:
        return _splitk_empty(stats, num_slots, num_segments, w_out, merge)
    if valid is None:
        valid = jnp.ones((r, cap), jnp.int32)
    pad = (-r) % chunk_rows
    if pad:
        table = jnp.pad(table, (0, pad))
        segment_ids = jnp.pad(segment_ids, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
        slot_ids = jnp.pad(slot_ids, (0, pad))
    composite = _masked_ids(slot_ids.astype(jnp.int32)[:, None]
                            * num_segments + segment_ids, valid)
    partials = _block_table_call(values_arena, composite, table, num_slots,
                                 num_segments, stats, num_cols, interpret,
                                 chunk_rows=chunk_rows)
    return merge_partials(partials) if merge else partials


def segment_aggregate_block_table_splitk_dense(
        values_arena: jnp.ndarray, segment_ids: jnp.ndarray,
        table: jnp.ndarray, num_segments: int, chunk_rows: int,
        valid: Optional[jnp.ndarray] = None,
        slot_ids: Optional[jnp.ndarray] = None,
        num_slots: Optional[int] = None,
        stats: Tuple[str, ...] = ALL_STATS,
        num_cols: Optional[int] = None,
        merge: bool = True):
    """Dense-backend split-K block-table fold: one pool-axis ``take``,
    then a ``vmap`` of the batched one-hot fold over ``k`` fixed-shape
    chunks of ``chunk_rows`` rows, merged (or returned raw with
    ``merge=False``) exactly as the Pallas path."""
    stats = norm_stats(stats)
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    p, cap, w = values_arena.shape
    w_out = num_cols if num_cols is not None else w
    r = table.shape[0]
    if slot_ids is None:
        slot_ids = jnp.arange(r, dtype=jnp.int32)
        if num_slots is None:
            num_slots = r
    elif num_slots is None:
        raise ValueError("num_slots is required when slot_ids is given")
    if r == 0 or num_slots == 0:
        return _splitk_empty(stats, num_slots, num_segments, w_out, merge)
    if valid is None:
        valid = jnp.ones((r, cap), jnp.int32)
    pad = (-r) % chunk_rows
    if pad:
        table = jnp.pad(table, (0, pad))
        segment_ids = jnp.pad(segment_ids, ((0, pad), (0, 0)))
        valid = jnp.pad(valid, ((0, pad), (0, 0)))
        slot_ids = jnp.pad(slot_ids, (0, pad))
    k = (r + pad) // chunk_rows
    vals = jnp.take(values_arena.astype(jnp.float32),
                    table.astype(jnp.int32), axis=0)
    if num_cols is not None:
        vals = vals[:, :, :num_cols]
    partials = jax.vmap(
        lambda v, sid, va, sl: segment_aggregate_batched_dense(
            v, sid, num_segments, valid=va, slot_ids=sl,
            num_slots=num_slots, stats=stats)
    )(vals.reshape(k, chunk_rows, cap, w_out),
      segment_ids.astype(jnp.int32).reshape(k, chunk_rows, cap),
      valid.astype(bool).reshape(k, chunk_rows, cap),
      slot_ids.astype(jnp.int32).reshape(k, chunk_rows))
    return merge_partials(partials) if merge else partials


def segment_aggregate_block_table_sharded(
        values_arena: jnp.ndarray, segment_ids: jnp.ndarray,
        table: jnp.ndarray, num_segments: int,
        valid: Optional[jnp.ndarray] = None,
        slot_ids: Optional[jnp.ndarray] = None,
        num_slots: Optional[int] = None, *, mesh,
        stats: Tuple[str, ...] = ALL_STATS,
        use_pallas: bool = False,
        interpret: bool = True,
        num_cols: Optional[int] = None,
        chunk_rows: int = 0):
    """Slot-sharded block-table fold over a 1-D mesh.

    Both the pool arena (slot axis) and the table rows partition across
    the mesh: shard ``d`` receives arena tile ``[pool_slots/D, ...]`` and
    its shard-major rows, and rewrites global pool slots / window slots to
    shard-local indices — the block table stays local to each shard, so
    the gather never crosses devices and the output is a pure slot-axis
    concatenation (psum-free, as in the stacked sharded fold). The
    executor's hash-based window placement plus the pool's per-shard slot
    ranges guarantee well-placed rows; a misplaced row (table entry or
    window slot outside the shard's ranges) is defensively masked invalid.
    ``chunk_rows > 0`` routes each shard's local fold through the split-K
    path (fixed-shape chunks, merged on-device per shard) — the output
    shape and sharding are unchanged.
    """
    stats = norm_stats(stats)
    p, cap, w = values_arena.shape
    r = table.shape[0]
    axis_name = mesh.axis_names[0]
    num_devices = mesh.shape[axis_name]
    if valid is None:
        valid = jnp.ones((r, cap), jnp.int32)
    if slot_ids is None:
        slot_ids = jnp.arange(r, dtype=jnp.int32)
        if num_slots is None:
            num_slots = r
    elif num_slots is None:
        raise ValueError("num_slots is required when slot_ids is given")
    if r % num_devices or num_slots % num_devices or p % num_devices:
        raise ValueError(
            f"rows ({r}), slots ({num_slots}) and pool slots ({p}) must "
            f"all divide the slot mesh ({num_devices} devices); pad with "
            "invalid rows (pack_rows_shard_major) and size the pool to "
            "the mesh")
    slots_per = num_slots // num_devices
    pool_per = p // num_devices

    def shard_fn(arena, sid, tbl, val, sl):
        base = jax.lax.axis_index(axis_name)
        local_tbl = tbl.astype(jnp.int32) - base * pool_per
        own_t = (local_tbl >= 0) & (local_tbl < pool_per)
        local_tbl = jnp.where(own_t, local_tbl, 0)
        local_sl = sl.astype(jnp.int32) - base * slots_per
        own_s = (local_sl >= 0) & (local_sl < slots_per)
        local_sl = jnp.where(own_s, local_sl, 0)
        val_own = val.astype(bool) & (own_t & own_s)[:, None]
        if chunk_rows > 0:
            if use_pallas:
                return segment_aggregate_block_table_splitk_pallas(
                    arena, sid, local_tbl, num_segments, chunk_rows,
                    valid=val_own, slot_ids=local_sl, num_slots=slots_per,
                    interpret=interpret, stats=stats, num_cols=num_cols)
            return segment_aggregate_block_table_splitk_dense(
                arena, sid, local_tbl, num_segments, chunk_rows,
                valid=val_own, slot_ids=local_sl, num_slots=slots_per,
                stats=stats, num_cols=num_cols)
        if use_pallas:
            return segment_aggregate_block_table_pallas(
                arena, sid, local_tbl, num_segments, valid=val_own,
                slot_ids=local_sl, num_slots=slots_per,
                interpret=interpret, stats=stats, num_cols=num_cols)
        return segment_aggregate_block_table_dense(
            arena, sid, local_tbl, num_segments, valid=val_own,
            slot_ids=local_sl, num_slots=slots_per, stats=stats,
            num_cols=num_cols)

    in_specs = (P(axis_name, None, None), P(axis_name, None),
                P(axis_name), P(axis_name, None), P(axis_name))
    out_specs = {k: (P(axis_name, None) if k == "count"
                     else P(axis_name, None, None))
                 for k in stats}
    f = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    return f(values_arena.astype(jnp.float32),
             segment_ids.astype(jnp.int32), table.astype(jnp.int32),
             valid.astype(jnp.int32), slot_ids.astype(jnp.int32))


def empty_batch_identity(num_slots: int, num_segments: int, w: int) -> dict:
    """Fold identity per (slot, segment) for an empty batch: zero
    sums/counts, +/-inf extrema. Shared by the public entry point and the
    ref oracle so the B == 0 contract cannot drift between them."""
    return {
        "sum": jnp.zeros((num_slots, num_segments, w), jnp.float32),
        "count": jnp.zeros((num_slots, num_segments), jnp.float32),
        "min": jnp.full((num_slots, num_segments, w), jnp.inf),
        "max": jnp.full((num_slots, num_segments, w), -jnp.inf),
    }


def next_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1). Shared by the batch executor's
    shape bucketing and the shard-major row packing below."""
    return 1 << max(n - 1, 0).bit_length()


def pack_rows_shard_major(slot_ids, num_devices: int, slots_per: int,
                          balance: bool = False) -> Tuple[list, int]:
    """Host-side row placement for the sharded fold.

    Default (ownership) mode groups row indices by owning shard
    (``slot // slots_per``) and picks the common power-of-two per-shard
    row count every shard pads to, so the
    ``[num_devices * rows_per_shard, ...]`` stack splits evenly under a
    ``shard_map`` over the leading axis. Returns
    ``(per_shard_row_indices, rows_per_shard)``.

    ``balance=True`` ignores slot ownership entirely and deals rows
    round-robin across shards — the split-K layout: a hot window's rows
    spread over every device instead of serializing on their owner, and
    per-shard row counts differ by at most one regardless of skew. Only
    valid for folds that reduce into full per-slot partials
    (``segment_aggregate_batched_splitk_sharded``); the ownership-masked
    kernels would silently drop balanced rows.
    """
    if balance:
        idx = np.arange(len(np.asarray(slot_ids)), dtype=np.int64)
        per = [idx[d::num_devices] for d in range(num_devices)]
    else:
        shard = np.asarray(slot_ids, np.int64) // max(slots_per, 1)
        per = [np.flatnonzero(shard == d) for d in range(num_devices)]
    rows_per_shard = next_pow2(max([len(p) for p in per] + [1]))
    return per, rows_per_shard


def segment_aggregate_batched_sharded(values: jnp.ndarray,
                                      segment_ids: jnp.ndarray,
                                      num_segments: int,
                                      valid: Optional[jnp.ndarray] = None,
                                      slot_ids: Optional[jnp.ndarray] = None,
                                      num_slots: Optional[int] = None,
                                      *, mesh,
                                      stats: Tuple[str, ...] = (
                                          "sum", "count", "min", "max"),
                                      use_pallas: bool = False,
                                      block_n: int = 512,
                                      interpret: bool = True):
    """Slot-sharded multi-window segment aggregation over a 1-D mesh.

    Same contract as ``segment_aggregate_batched_pallas`` with one layout
    precondition: rows are **shard-major** — row ``r`` belongs to the
    device ``r // (B / num_devices)``, and its (global) slot id must fall
    in that device's range ``[d*slots_per, (d+1)*slots_per)`` where
    ``slots_per = num_slots / num_devices`` (``pack_rows_shard_major``
    produces this layout). Each shard reduces its own rows into its own
    slot tile; the 2-D ``(slot, key)`` layout makes the tiles disjoint,
    so the gathered output is a pure concatenation along the slot axis —
    **no psum**. Misplaced rows are masked invalid inside the shard (they
    contribute nothing) instead of aliasing into a resident slot.
    """
    stats = norm_stats(stats)
    b, n, w = values.shape
    axis_name = mesh.axis_names[0]
    num_devices = mesh.shape[axis_name]
    if valid is None:
        valid = jnp.ones((b, n), bool)
    if slot_ids is None:
        slot_ids = jnp.arange(b, dtype=jnp.int32)
        if num_slots is None:
            num_slots = b
    elif num_slots is None:
        raise ValueError("num_slots is required when slot_ids is given")
    if b % num_devices or num_slots % num_devices:
        raise ValueError(
            f"rows ({b}) and slots ({num_slots}) must both divide the "
            f"slot mesh ({num_devices} devices); pad with invalid rows / "
            "unused slots (pack_rows_shard_major)")
    slots_per = num_slots // num_devices

    def shard_fn(v, sid, val, sl):
        base = jax.lax.axis_index(axis_name) * slots_per
        local = sl.astype(jnp.int32) - base
        own = (local >= 0) & (local < slots_per)
        local = jnp.where(own, local, 0)
        val_own = val.astype(bool) & own[:, None]
        if use_pallas:
            return segment_aggregate_batched_pallas(
                v, sid, num_segments, valid=val_own, slot_ids=local,
                num_slots=slots_per, block_n=block_n, interpret=interpret,
                stats=stats)
        return segment_aggregate_batched_dense(
            v, sid, num_segments, valid=val_own, slot_ids=local,
            num_slots=slots_per, stats=stats)

    in_specs = (P(axis_name, None, None), P(axis_name, None),
                P(axis_name, None), P(axis_name))
    out_specs = {k: (P(axis_name, None) if k == "count"
                     else P(axis_name, None, None))
                 for k in stats}
    f = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    return f(values.astype(jnp.float32), segment_ids.astype(jnp.int32),
             valid.astype(bool), slot_ids.astype(jnp.int32))


def segment_aggregate_batched_splitk_sharded(
        values: jnp.ndarray,
        segment_ids: jnp.ndarray,
        num_segments: int,
        valid: Optional[jnp.ndarray] = None,
        slot_ids: Optional[jnp.ndarray] = None,
        num_slots: Optional[int] = None,
        *, mesh,
        stats: Tuple[str, ...] = ALL_STATS,
        use_pallas: bool = False,
        block_n: int = 512,
        interpret: bool = True):
    """Row-balanced (split-K) sharded fold over a 1-D mesh.

    The distributed half of the split-K idiom: rows are dealt across the
    mesh with NO slot-ownership precondition
    (``pack_rows_shard_major(..., balance=True)``), each device folds its
    rows into a **full** ``[num_slots, S, ...]`` partial accumulator, and
    the ``D`` per-device partials merge through each stat's identity
    after the ``shard_map`` (``merge_partials`` over the stacked leading
    device axis). Compared to the slot-ownership variant this trades a
    ``D``-times-larger accumulator footprint for perfect row balance: a
    Zipf-hot window whose rows dominate the batch folds on every device
    instead of serializing on its owning shard. ``num_slots`` need not
    divide the mesh — only the row count must.

    Only safe for operators whose batch contract reduces through plain
    per-slot accumulators (``WindowOperator.supports_splitk``); kernels
    that mask rows by slot ownership (the bigram scatter) would silently
    drop balanced rows.
    """
    stats = norm_stats(stats)
    b, n, w = values.shape
    axis_name = mesh.axis_names[0]
    num_devices = mesh.shape[axis_name]
    if valid is None:
        valid = jnp.ones((b, n), bool)
    if slot_ids is None:
        slot_ids = jnp.arange(b, dtype=jnp.int32)
        if num_slots is None:
            num_slots = b
    elif num_slots is None:
        raise ValueError("num_slots is required when slot_ids is given")
    if b % num_devices:
        raise ValueError(
            f"rows ({b}) must divide the slot mesh ({num_devices} "
            "devices); pad with invalid rows "
            "(pack_rows_shard_major(balance=True))")

    def shard_fn(v, sid, val, sl):
        if use_pallas:
            part = segment_aggregate_batched_pallas(
                v, sid, num_segments, valid=val, slot_ids=sl,
                num_slots=num_slots, block_n=block_n,
                interpret=interpret, stats=stats)
        else:
            part = segment_aggregate_batched_dense(
                v, sid, num_segments, valid=val, slot_ids=sl,
                num_slots=num_slots, stats=stats)
        # grow the leading device axis the out_specs stack over
        return {s: o[None] for s, o in part.items()}

    in_specs = (P(axis_name, None, None), P(axis_name, None),
                P(axis_name, None), P(axis_name))
    out_specs = {k: (P(axis_name, None, None) if k == "count"
                     else P(axis_name, None, None, None))
                 for k in stats}
    f = jax.shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=False)
    partials = f(values.astype(jnp.float32), segment_ids.astype(jnp.int32),
                 valid.astype(bool), slot_ids.astype(jnp.int32))
    return merge_partials(partials)
