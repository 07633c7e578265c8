"""Public kernel entry points: jit'd wrappers with backend dispatch.

``backend``:
  'pallas'     real Mosaic lowering (TPU)
  'interpret'  Pallas interpreter (CPU validation — this container)
  'ref'        pure-jnp oracle (numerics baseline)
  'auto'       pallas on TPU; elsewhere interpret (``segment_aggregate``
               and the attention/scan kernels) or the dense one-hot jnp
               fold (the batched and block-table folds)

Every segment fold records the backend it resolved to in
``resolved_backends`` — an on-chip run reads it to prove that no fold
fell back to the dense or interpreter path.
"""
from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.decode_attention import decode_attention_paged_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention_bwd import flash_attention_bwd_pallas
from repro.kernels.segment_aggregate import (
    empty_batch_identity as _empty_batch_identity,
    norm_stats as _norm_stats,
    segment_aggregate_batched_dense, segment_aggregate_batched_pallas,
    segment_aggregate_batched_sharded,
    segment_aggregate_batched_splitk_sharded,
    segment_aggregate_block_table_dense,
    segment_aggregate_block_table_pallas,
    segment_aggregate_block_table_sharded,
    segment_aggregate_block_table_splitk_dense,
    segment_aggregate_block_table_splitk_pallas, segment_aggregate_pallas,
)
from repro.kernels.ssd_scan import ssd_scan_pallas


def _resolve(backend: str, off_tpu: str = "interpret") -> str:
    if backend != "auto":
        return backend
    platform = jax.devices()[0].platform
    return "pallas" if platform == "tpu" else off_tpu


#: (fold entry point, backend) -> traces that resolved to it. A jitted
#: fold resolves once per compiled shape, so this counts compiled
#: variants, not calls.
resolved_backends: collections.Counter = collections.Counter()


def _resolve_fold(entry: str, backend: str, off_tpu: str = "dense") -> str:
    be = _resolve(backend, off_tpu)
    resolved_backends[(entry, be)] += 1
    return be


@functools.partial(jax.jit, static_argnames=("num_segments", "backend",
                                             "block_n", "stats"))
def segment_aggregate(values, segment_ids, num_segments: int, valid=None,
                      backend: str = "auto", block_n: int = 512,
                      stats: tuple = ("sum", "count", "min", "max")):
    """``stats`` selects which aggregates the kernel materializes — the
    selection reaches the Pallas out_shapes, so sum/count-only callers
    skip the min/max VPU broadcast-reduce on the Mosaic path too."""
    stats = _norm_stats(stats)
    be = _resolve_fold("segment_aggregate", backend, off_tpu="interpret")
    if be == "ref":
        out = _ref.ref_segment_aggregate(values, segment_ids, num_segments,
                                         valid)
        return {k: v for k, v in out.items() if k in stats}
    return segment_aggregate_pallas(values, segment_ids, num_segments,
                                    valid=valid, block_n=block_n,
                                    interpret=(be == "interpret"),
                                    stats=stats)


@functools.partial(jax.jit, static_argnames=("num_segments", "num_slots",
                                             "backend", "block_n",
                                             "stats", "mesh", "splitk"))
def segment_aggregate_batched(values, segment_ids, num_segments: int,
                              valid=None, slot_ids=None,
                              num_slots: Optional[int] = None,
                              backend: str = "auto", block_n: int = 512,
                              stats: tuple = ("sum", "count", "min",
                                              "max"),
                              mesh=None, splitk: int = 0):
    """Batched multi-window reduce-by-key: values [B, N, W], ids [B, N],
    slot_ids [B] -> aggregates [num_slots, num_segments, ...] in one pass.

    The engine's batched execution path folds every due window through a
    single launch of this op. ``backend='auto'`` resolves to Mosaic on
    TPU and the dense one-hot jnp formulation elsewhere (identical math;
    XLA:CPU scatters and the Pallas interpreter are both validation-only
    speeds). ``stats`` selects which aggregates to materialize — folds
    that only need sum/count skip the min/max work.

    ``mesh`` (a 1-D device mesh; static, hashable) routes the fold
    through the slot-sharded variant: window slots partition across the
    mesh and each device reduces only its own shard-major rows —
    psum-free, since slots are disjoint. Rows/slots must divide the mesh
    and rows must be packed shard-major (``pack_rows_shard_major``). The
    ``'ref'`` backend ignores the mesh: it is the unsharded oracle the
    sharded path is validated against.

    ``splitk > 0`` with a mesh switches to the **row-balanced** split-K
    variant: rows are dealt across devices with no ownership
    precondition (``pack_rows_shard_major(balance=True)``), each device
    folds a full per-slot partial, and the partials merge after the
    shard_map. Only rows must divide the mesh; slots are unconstrained.
    Callers must check ``WindowOperator.supports_splitk`` — ownership-
    masking folds would drop balanced rows. Without a mesh ``splitk`` is
    a no-op here (single-device chunking lives on the block-table path).
    """
    stats = _norm_stats(stats)
    b = values.shape[0]
    ns = num_slots if num_slots is not None else \
        (b if slot_ids is None else None)
    if ns is None:
        raise ValueError("num_slots is required when slot_ids is given")
    if b == 0 or ns == 0:
        # empty-batch edge: no degenerate [0, ...] kernel launch — return
        # the fold identity (zero sum/count, +/-inf extrema) directly
        empty = _empty_batch_identity(ns, num_segments, values.shape[2])
        return {k: v for k, v in empty.items() if k in stats}
    be = _resolve_fold("segment_aggregate_batched", backend)
    if mesh is not None and be != "ref" and mesh.size > 1:
        if splitk > 0:
            return segment_aggregate_batched_splitk_sharded(
                values, segment_ids, num_segments, valid=valid,
                slot_ids=slot_ids, num_slots=ns, mesh=mesh,
                stats=stats, use_pallas=(be in ("pallas", "interpret")),
                block_n=block_n, interpret=(be == "interpret"))
        return segment_aggregate_batched_sharded(
            values, segment_ids, num_segments, valid=valid,
            slot_ids=slot_ids, num_slots=num_slots, mesh=mesh,
            stats=stats, use_pallas=(be in ("pallas", "interpret")),
            block_n=block_n, interpret=(be == "interpret"))
    if be == "dense":
        return segment_aggregate_batched_dense(
            values, segment_ids, num_segments, valid=valid,
            slot_ids=slot_ids, num_slots=num_slots, stats=stats)
    if be == "ref":
        out = _ref.ref_segment_aggregate_batched(
            values, segment_ids, num_segments, valid=valid,
            slot_ids=slot_ids, num_slots=num_slots)
        return {k: v for k, v in out.items() if k in stats}
    return segment_aggregate_batched_pallas(
        values, segment_ids, num_segments, valid=valid,
        slot_ids=slot_ids, num_slots=num_slots, block_n=block_n,
        interpret=(be == "interpret"), stats=stats)


@functools.partial(jax.jit, static_argnames=("num_segments", "num_slots",
                                             "backend", "stats", "mesh",
                                             "num_cols"))
def segment_aggregate_block_table(values_arena, segment_ids, table,
                                  num_segments: int, valid=None,
                                  slot_ids=None,
                                  num_slots: Optional[int] = None,
                                  backend: str = "auto",
                                  stats: tuple = ("sum", "count", "min",
                                                  "max"),
                                  mesh=None,
                                  num_cols: Optional[int] = None):
    """Batched multi-window reduce-by-key over a persistent block pool.

    values_arena [pool_slots, cap, W] (the device arena the staging layer
    fills), table [R] i32 pool-slot indices, segment_ids [R, cap] i32,
    slot_ids [R] window slots -> aggregates [num_slots, num_segments, ...]
    in one pass. This is the zero-copy gather path of the batched engine
    fold: rows are event tiles *referenced* out of the arena rather than
    stacked into a fresh tensor — an in-kernel scalar-prefetch DMA on the
    Mosaic backend, a single take along the pool axis on the dense
    backend. Shapes depend only on the (pow2-padded) table length and the
    fixed arena, so the jit cache stays O(log batch).

    ``mesh`` routes through the sharded variant: arena and table both
    partition across the mesh and each shard gathers only from its own
    arena tile (see ``segment_aggregate_block_table_sharded``). The
    ``'ref'`` backend ignores the mesh — it is the unsharded oracle.
    ``num_cols`` restricts the fold to the leading value columns, sliced
    AFTER the row gather (width-selecting operators pass the full arena
    — never an arena-wide slice copy).
    """
    stats = _norm_stats(stats)
    r = table.shape[0]
    ns = num_slots if num_slots is not None else \
        (r if slot_ids is None else None)
    if ns is None:
        raise ValueError("num_slots is required when slot_ids is given")
    if r == 0 or ns == 0:
        w_out = num_cols if num_cols is not None else values_arena.shape[2]
        empty = _empty_batch_identity(ns, num_segments, w_out)
        return {k: v for k, v in empty.items() if k in stats}
    be = _resolve_fold("segment_aggregate_block_table", backend)
    if mesh is not None and be != "ref" and mesh.size > 1:
        return segment_aggregate_block_table_sharded(
            values_arena, segment_ids, table, num_segments, valid=valid,
            slot_ids=slot_ids, num_slots=num_slots, mesh=mesh, stats=stats,
            use_pallas=(be in ("pallas", "interpret")),
            interpret=(be == "interpret"), num_cols=num_cols)
    if be == "dense":
        return segment_aggregate_block_table_dense(
            values_arena, segment_ids, table, num_segments, valid=valid,
            slot_ids=slot_ids, num_slots=num_slots, stats=stats,
            num_cols=num_cols)
    if be == "ref":
        out = _ref.ref_segment_aggregate_block_table(
            values_arena, segment_ids, table, num_segments, valid=valid,
            slot_ids=slot_ids, num_slots=num_slots, num_cols=num_cols)
        return {k: v for k, v in out.items() if k in stats}
    return segment_aggregate_block_table_pallas(
        values_arena, segment_ids, table, num_segments, valid=valid,
        slot_ids=slot_ids, num_slots=num_slots,
        interpret=(be == "interpret"), stats=stats, num_cols=num_cols)


@functools.partial(jax.jit, static_argnames=("num_segments", "chunk_rows",
                                             "num_slots", "backend",
                                             "stats", "mesh", "num_cols"))
def segment_aggregate_block_table_splitk(values_arena, segment_ids, table,
                                         num_segments: int, chunk_rows: int,
                                         valid=None, slot_ids=None,
                                         num_slots: Optional[int] = None,
                                         backend: str = "auto",
                                         stats: tuple = ("sum", "count",
                                                         "min", "max"),
                                         mesh=None,
                                         num_cols: Optional[int] = None):
    """Split-K block-table fold: the block-table gather of
    ``segment_aggregate_block_table`` with the pool axis partitioned into
    fixed-shape chunks of ``chunk_rows`` rows, per-chunk partial
    accumulators, and an on-device identity merge (flash-decoding's
    ``mid_o`` second half).

    Launch shapes depend only on ``chunk_rows`` and the chunk count —
    never the raw batch size — so an executor that decomposes variable
    batches into a fixed repertoire of chunk counts folds ANY batch with
    zero recompiles, and one hot window's rows spread across chunk
    programs instead of serializing a single segment stripe. ``mesh``
    routes through the sharded block-table variant with per-shard
    split-K local folds (same ownership layout as the plain sharded op).
    The ``'ref'`` backend is the chunk-looped oracle
    (``ref_segment_aggregate_block_table_splitk``) the other backends
    are validated against.
    """
    stats = _norm_stats(stats)
    if chunk_rows < 1:
        raise ValueError(f"chunk_rows must be >= 1, got {chunk_rows}")
    r = table.shape[0]
    ns = num_slots if num_slots is not None else \
        (r if slot_ids is None else None)
    if ns is None:
        raise ValueError("num_slots is required when slot_ids is given")
    if r == 0 or ns == 0:
        w_out = num_cols if num_cols is not None else values_arena.shape[2]
        empty = _empty_batch_identity(ns, num_segments, w_out)
        return {k: v for k, v in empty.items() if k in stats}
    be = _resolve_fold("segment_aggregate_block_table_splitk", backend)
    if mesh is not None and be != "ref" and mesh.size > 1:
        return segment_aggregate_block_table_sharded(
            values_arena, segment_ids, table, num_segments, valid=valid,
            slot_ids=slot_ids, num_slots=num_slots, mesh=mesh, stats=stats,
            use_pallas=(be in ("pallas", "interpret")),
            interpret=(be == "interpret"), num_cols=num_cols,
            chunk_rows=chunk_rows)
    if be == "dense":
        return segment_aggregate_block_table_splitk_dense(
            values_arena, segment_ids, table, num_segments, chunk_rows,
            valid=valid, slot_ids=slot_ids, num_slots=num_slots,
            stats=stats, num_cols=num_cols)
    if be == "ref":
        out = _ref.ref_segment_aggregate_block_table_splitk(
            values_arena, segment_ids, table, num_segments, chunk_rows,
            valid=valid, slot_ids=slot_ids, num_slots=num_slots,
            num_cols=num_cols)
        return {k: v for k, v in out.items() if k in stats}
    return segment_aggregate_block_table_splitk_pallas(
        values_arena, segment_ids, table, num_segments, chunk_rows,
        valid=valid, slot_ids=slot_ids, num_slots=num_slots,
        interpret=(be == "interpret"), stats=stats, num_cols=num_cols)


@functools.partial(jax.jit, static_argnames=("causal", "window", "backend",
                                             "block_q", "block_k"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    backend: str = "auto", block_q: int = 512,
                    block_k: int = 512):
    be = _resolve(backend)
    if be == "ref":
        return _ref.ref_flash_attention(q, k, v, causal=causal, window=window)
    b, sq, h, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, sq)
    while sq % bq:
        bq //= 2
    bk = min(block_k, sk)
    while sk % bk:
        bk //= 2
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  block_q=max(bq, 1), block_k=max(bk, 1),
                                  interpret=(be == "interpret"))


@functools.partial(jax.jit, static_argnames=("backend",))
def decode_attention_paged(q, k_pages, v_pages, block_table, seq_lens,
                           backend: str = "auto"):
    be = _resolve(backend)
    if be == "ref":
        return _ref.ref_decode_attention_paged(q, k_pages, v_pages,
                                               block_table, seq_lens)
    return decode_attention_paged_pallas(q, k_pages, v_pages, block_table,
                                         seq_lens,
                                         interpret=(be == "interpret"))


@functools.partial(jax.jit, static_argnames=("chunk", "head_block",
                                             "backend"))
def ssd_chunk_scan(xdt, a, B, C, chunk: int = 256, head_block: int = 8,
                   backend: str = "auto"):
    be = _resolve(backend)
    if be == "ref":
        y, _ = _ref.ref_ssd_chunk_scan(xdt, a, B, C, chunk)
        return y
    h = xdt.shape[2]
    hb = min(head_block, h)
    while h % hb:
        hb //= 2
    return ssd_scan_pallas(xdt, a, B, C, chunk, head_block=max(hb, 1),
                           interpret=(be == "interpret"))


# ---------------------------------------------------------------------------
# Differentiable flash attention (custom VJP over the fwd + bwd kernels)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_vjp(q, k, v, causal: bool = True, window: int = 0,
                        block_q: int = 512, block_k: int = 512,
                        interpret: bool = True):
    """flash_attention with a flash backward: neither pass materializes the
    [Sq, Sk] probability matrix. q [B,Sq,H,D]; k,v [B,Sk,Hkv,D]."""
    o, _ = _fa_fwd(q, k, v, causal, window, block_q, block_k, interpret)
    return o


def _fa_fwd(q, k, v, causal, window, block_q, block_k, interpret):
    b, sq, h, d = q.shape
    bq = min(block_q, sq)
    bk = min(block_k, k.shape[1])
    o, lse = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                    block_q=bq, block_k=bk,
                                    interpret=interpret, return_lse=True)
    return o, (q, k, v, o, lse)


def _fa_bwd(causal, window, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    # flatten heads; broadcast kv over the GQA group for the bwd kernels
    qf = q.reshape(b, sq, hkv, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b * hkv * g, sq, d)
    of = o.reshape(b, sq, hkv, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b * hkv * g, sq, d)
    dof = do.reshape(b, sq, hkv, g, d).transpose(0, 2, 3, 1, 4) \
        .reshape(b * hkv * g, sq, d)
    kf = jnp.repeat(k.transpose(0, 2, 1, 3), g, axis=1) \
        .reshape(b * hkv * g, sk, d)
    vf = jnp.repeat(v.transpose(0, 2, 1, 3), g, axis=1) \
        .reshape(b * hkv * g, sk, d)
    dqf, dkf, dvf = flash_attention_bwd_pallas(
        qf, kf, vf, of, dof, lse, causal=causal, window=window,
        block_q=bq, block_k=bk, interpret=interpret)
    dq = dqf.reshape(b, hkv, g, sq, d).transpose(0, 3, 1, 2, 4) \
        .reshape(b, sq, h, d)
    # sum group gradients back onto the shared kv heads
    dk = dkf.reshape(b, hkv, g, sk, d).sum(axis=2).transpose(0, 2, 1, 3)
    dv = dvf.reshape(b, hkv, g, sk, d).sum(axis=2).transpose(0, 2, 1, 3)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


flash_attention_vjp.defvjp(_fa_fwd, _fa_bwd)
