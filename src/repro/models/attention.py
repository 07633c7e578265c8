"""Attention: blocked (flash-style) for train/prefill, direct for decode.

The blocked path keeps peak memory at one ``[B, block_q, H, block_k]`` score
tile via a two-level ``lax.scan`` with online softmax — this is both the XLA
production path for the dry-run and the numerical oracle the Pallas
``flash_attention`` kernel is tested against.

GQA divisibility: when the TP axis exceeds ``num_kv_heads``, K/V activations
are repeated at compute time (``kv_repeat``) so the stored-head axis shards
evenly; parameters keep the true KV head count.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.models.layers import apply_rope, dense_apply, dense_init

NEG_INF = jnp.float32(-1e30)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, dtype, cross: bool = False):
    d = cfg.d_model
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    q_p, q_s = dense_init(ks[0], d, (h, dh), (shd.FSDP, shd.HEADS, None),
                          dtype, use_bias=cfg.use_bias)
    k_p, k_s = dense_init(ks[1], d, (hkv, dh),
                          (shd.FSDP, shd.KV_PARAM_HEADS, None),
                          dtype, use_bias=cfg.use_bias)
    v_p, v_s = dense_init(ks[2], d, (hkv, dh),
                          (shd.FSDP, shd.KV_PARAM_HEADS, None),
                          dtype, use_bias=cfg.use_bias)
    o_p, o_s = dense_init(ks[3], h * dh, (d,), (shd.HEADS, shd.FSDP), dtype,
                          scale=1.0 / math.sqrt(h * dh), use_bias=cfg.use_bias)
    # o weight reshaped to [h, dh, d] so the head axis shards
    o_p = {"w": o_p["w"].reshape(h, dh, d), **{k: v for k, v in o_p.items() if k == "b"}}
    o_s = {"w": (shd.HEADS, None, shd.FSDP), **{k: (None,) for k in o_p if k == "b"}}
    return ({"q": q_p, "k": k_p, "v": v_p, "o": o_p},
            {"q": q_s, "k": k_s, "v": v_s, "o": o_s})


def _repeat_kv(kv: jnp.ndarray, repeat: int) -> jnp.ndarray:
    """[B, S, Hkv, D] -> [B, S, Hkv*repeat, D] (tile so groups stay contiguous)."""
    if repeat == 1:
        return kv
    b, s, h, d = kv.shape
    return jnp.repeat(kv, repeat, axis=2)


# ---------------------------------------------------------------------------
# Blocked attention with online softmax
# ---------------------------------------------------------------------------

def _pick_block(s: int, target: int) -> int:
    b = min(target, s)
    while s % b:
        b //= 2
    return max(b, 1)


def blocked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool, window: int = 0, q_offset: int = 0,
                      block_q: int = 512, block_k: int = 512,
                      kv_valid_len: Optional[jnp.ndarray] = None,
                      causal_skip: bool = False) -> jnp.ndarray:
    """Online-softmax attention.

    q: [B, Sq, Hq, D]; k, v: [B, Sk, Hs, D] with Hq % Hs == 0.
    ``window > 0``: causal sliding window (token i sees [i-window+1, i]) and
    the kv scan is *structurally* limited to the window span (sub-quadratic).
    ``kv_valid_len``: optional [B] count of valid kv positions (padding mask).
    ``causal_skip``: §Perf optimization — unroll the q-block loop so each q
    block scans only its (statically known) non-masked kv prefix, halving
    executed attention FLOPs for causal full attention.
    Returns [B, Sq, Hq, D] in q.dtype.
    """
    b, sq, hq, dh = q.shape
    _, sk, hs, _ = k.shape
    g = hq // hs
    scale = 1.0 / math.sqrt(dh)
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    nq = sq // bq

    qg = q.reshape(b, sq, hs, g, dh)

    def q_block_body(qi, _, n_kv_static: int = 0):
        q_blk = jax.lax.dynamic_slice_in_dim(qg, qi * bq, bq, axis=1)
        q_blk = (q_blk.astype(jnp.float32) * scale).astype(q.dtype)
        q_pos = q_offset + qi * bq + jnp.arange(bq)          # [bq]

        if n_kv_static:
            # causal-skip path: qi is a python int; scan only the blocks
            # this q block can attend to
            starts = jnp.arange(n_kv_static) * bk
        elif window > 0:
            # kv span: [q_start - window + 1, q_start + bq) clamped
            n_off = (window + bq - 1) // bk + 1
            base = qi * bq + bq - 1 - (n_off - 1) * bk

            def kv_starts(o):
                return jnp.clip(base + o * bk, 0, sk - bk)
            offsets = jnp.arange(n_off)
            starts = jax.vmap(kv_starts)(offsets)
        else:
            n_off = sk // bk
            starts = jnp.arange(n_off) * bk

        def kv_step(carry, start):
            m, l, acc = carry
            k_blk = jax.lax.dynamic_slice_in_dim(k, start, bk, axis=1)
            v_blk = jax.lax.dynamic_slice_in_dim(v, start, bk, axis=1)
            k_pos = start + jnp.arange(bk)                   # [bk]
            # scores: [b, hs, g, bq, bk]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32)
            mask = jnp.ones((bq, bk), dtype=bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window > 0:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            if kv_valid_len is not None:
                s = jnp.where(
                    (k_pos[None, :] < kv_valid_len[:, None])[:, None, None, None, :],
                    s, NEG_INF)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            blk_max = jnp.max(s, axis=-1)                    # [b,hs,g,bq]
            new_m = jnp.maximum(m, blk_max)
            corr = jnp.exp(m - new_m)
            p = jnp.exp(s - new_m[..., None])                # fp32
            new_l = l * corr + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(v.dtype), v_blk,
                            preferred_element_type=jnp.float32)
            new_acc = acc * corr[..., None] + pv
            return (new_m, new_l, new_acc), None

        m0 = jnp.full((b, hs, g, bq), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((b, hs, g, bq), dtype=jnp.float32)
        a0 = jnp.zeros((b, hs, g, bq, dh), dtype=jnp.float32)
        # checkpoint the kv step: backward recomputes the score tile instead
        # of saving [b,hs,g,bq,bk] per step (flash-attention memory shape)
        (m, l, acc), _ = jax.lax.scan(jax.checkpoint(kv_step), (m0, l0, a0),
                                      starts)
        out = acc / jnp.maximum(l, 1e-30)[..., None]         # [b,hs,g,bq,dh]
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, bq, hq, dh)
        return qi + 1, out.astype(q.dtype)

    if causal_skip and causal and window == 0 and q_offset == 0 \
            and bq == bk:
        # unrolled q loop with per-block static kv extents: executed score
        # FLOPs drop from nq*nk to nq*(nq+1)/2 tiles (the causal half)
        outs = []
        ck = jax.checkpoint(q_block_body, static_argnums=(2,))
        for qi in range(nq):
            _, out = ck(qi, None, qi + 1)
            outs.append(out)
        return jnp.concatenate(outs, axis=1)

    # checkpoint per q block: only per-block outputs are saved across the
    # outer scan; the inner kv scan re-runs during that block's backward
    _, blocks = jax.lax.scan(jax.checkpoint(q_block_body), 0, None, length=nq)
    # blocks: [nq, b, bq, hq, dh] -> [b, sq, hq, dh]
    return blocks.transpose(1, 0, 2, 3, 4).reshape(b, sq, hq, dh)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     valid_mask: jnp.ndarray) -> jnp.ndarray:
    """Single-step attention against a cache.

    q: [B, 1, Hq, D]; caches: [B, S, Hs, D]; valid_mask: [B, S] bool.
    """
    b, _, hq, dh = q.shape
    _, s, hs, _ = k_cache.shape
    g = hq // hs
    scale = 1.0 / math.sqrt(dh)
    qg = q.reshape(b, hs, g, dh)
    scores = jnp.einsum("bhgd,bshd->bhgs", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    scores = jnp.where(valid_mask[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, hq, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention block (qkv -> rope -> attend -> o)
# ---------------------------------------------------------------------------

def attn_forward(params, x, cfg: ModelConfig, *, positions,
                 kv_repeat: int = 1, causal: bool = True,
                 window: int = 0, return_kv: bool = False,
                 xattn_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                 kv_valid_len=None, causal_skip: bool = False):
    """Train/prefill attention. x: [B, S, D]. positions: [B, S].

    ``xattn_kv``: precomputed (k, v) for cross-attention (skips self kv).
    Returns (out, (k, v)) — (k, v) are the *stored* (possibly repeated,
    post-RoPE) heads for cache reuse, or None unless requested.
    """
    cd = x.dtype
    q = dense_apply(params["q"], x, cd)                      # [B,S,H,dh]
    q = shd.constrain(q, shd.BATCH, None, shd.HEADS, None)
    if xattn_kv is None:
        k = dense_apply(params["k"], x, cd)
        v = dense_apply(params["v"], x, cd)
        if cfg.rope_theta > 0:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
        k = _repeat_kv(k, kv_repeat)
        v = _repeat_kv(v, kv_repeat)
        k = shd.constrain(k, shd.BATCH, None, shd.KV_HEADS, None)
        v = shd.constrain(v, shd.BATCH, None, shd.KV_HEADS, None)
    else:
        k, v = xattn_kv
        if cfg.rope_theta > 0:
            q = apply_rope(q, positions, cfg.rope_theta)
    out = blocked_attention(q, k, v, causal=causal, window=window,
                            kv_valid_len=kv_valid_len,
                            causal_skip=causal_skip)
    out = shd.constrain(out, shd.BATCH, None, shd.HEADS, None)
    y = dense_apply(params["o"], out, cd, contract_dims=2)
    y = shd.constrain(y, shd.BATCH, None, None)
    kv = (k, v) if (return_kv or xattn_kv is not None) else None
    return y, kv


def _quantize_kv(x):
    """[..., dh] -> (int8 values, per-vector scale)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0 + 1e-9
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(x.dtype)


def _shard_map_dus_write(cache, new, slot, mesh, batch_axes):
    """Per-shard dynamic-update-slice on a sequence-sharded cache: each
    model shard writes the token only if the slot lies in its local range —
    no full-cache copy pass (SPerf C3)."""
    from jax.sharding import PartitionSpec as P
    bspec = batch_axes if batch_axes else None

    def write(c_loc, n_loc, s):
        s_loc = c_loc.shape[1]
        idx = jax.lax.axis_index("model")
        local = jnp.asarray(s, jnp.int32) - idx * s_loc
        in_range = (local >= 0) & (local < s_loc)

        def do(c):
            return jax.lax.dynamic_update_slice_in_dim(
                c, n_loc.astype(c.dtype), jnp.clip(local, 0, s_loc - 1),
                axis=1)

        return jax.lax.cond(in_range, do, lambda c: c, c_loc)

    nd_tail = cache.ndim - 2
    cspec = P(bspec, "model", *([None] * nd_tail))
    nspec = P(bspec, None, *([None] * nd_tail))
    return jax.shard_map(write, mesh=mesh, in_specs=(cspec, nspec, P()),
                         out_specs=cspec, check_vma=False)(cache, new, slot)


def attn_decode(params, x, cfg: ModelConfig, *, cache_k, cache_v, cache_pos,
                kv_repeat: int = 1, window: int = 0,
                xattn_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                xattn_len=None, kv_scales=None, dus_write: bool = False):
    """Decode one token. x: [B, 1, D]; caches [B, S_cache, Hs, dh];
    cache_pos: scalar int32 — absolute position of the new token.

    Window archs use a ring buffer of size S_cache == window.
    ``kv_scales``: (k_scale, v_scale) for an int8-quantized cache (§Perf) —
    values are dequantized for the score/readout matmuls and new tokens are
    quantized on write. Returns (out, cache_k, cache_v, scales_or_None).
    """
    cd = x.dtype
    b = x.shape[0]
    pos = jnp.full((b, 1), cache_pos, dtype=jnp.int32)
    q = dense_apply(params["q"], x, cd)
    if cfg.rope_theta > 0:
        q = apply_rope(q, pos, cfg.rope_theta)
    q = shd.constrain(q, shd.BATCH, None, shd.HEADS, None)

    if xattn_kv is not None:
        k_all, v_all = xattn_kv
        s = k_all.shape[1]
        valid = jnp.arange(s)[None, :] < (
            xattn_len[:, None] if xattn_len is not None
            else jnp.full((b, 1), s, jnp.int32))
        out = decode_attention(q, k_all, v_all, valid)
        out = shd.constrain(out, shd.BATCH, None, shd.HEADS, None)
        y = dense_apply(params["o"], out, cd, contract_dims=2)
        return shd.constrain(y, shd.BATCH, None, None), cache_k, cache_v, None

    k_new = dense_apply(params["k"], x, cd)
    v_new = dense_apply(params["v"], x, cd)
    if cfg.rope_theta > 0:
        k_new = apply_rope(k_new, pos, cfg.rope_theta)
    k_new = _repeat_kv(k_new, kv_repeat)
    v_new = _repeat_kv(v_new, kv_repeat)
    k_scale_new = v_scale_new = None
    if kv_scales is not None:
        k_new, k_scale_new = _quantize_kv(k_new)
        v_new, v_scale_new = _quantize_kv(v_new)

    s_cache = cache_k.shape[1]
    slot = jnp.where(window > 0, cache_pos % s_cache, cache_pos)
    slot = jnp.asarray(slot, jnp.int32)
    ctx = shd.current_ctx()
    seq_sharded = (ctx is not None and ctx.profile is not None
                   and ctx.profile.kv_seq_shard)
    if seq_sharded and dus_write:
        batch_axes = ctx.profile.batch_axes
        cache_k = _shard_map_dus_write(cache_k, k_new, slot, ctx.mesh,
                                       batch_axes)
        cache_v = _shard_map_dus_write(cache_v, v_new, slot, ctx.mesh,
                                       batch_axes)
    elif seq_sharded:
        # masked write: elementwise select shards cleanly over the sequence
        # axis (a plain dynamic-update-slice on a sharded dim would force
        # SPMD to replicate the cache)
        sel = (jnp.arange(s_cache, dtype=jnp.int32) == slot)[None, :, None, None]
        cache_k = jnp.where(sel, k_new.astype(cache_k.dtype), cache_k)
        cache_v = jnp.where(sel, v_new.astype(cache_v.dtype), cache_v)
    else:
        cache_k = jax.lax.dynamic_update_slice_in_dim(
            cache_k, k_new.astype(cache_k.dtype), slot, axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(
            cache_v, v_new.astype(cache_v.dtype), slot, axis=1)
    cache_k = shd.constrain(cache_k, shd.BATCH, shd.KV_SEQ, shd.KV_HEADS, None)
    cache_v = shd.constrain(cache_v, shd.BATCH, shd.KV_SEQ, shd.KV_HEADS, None)

    new_scales = None
    if kv_scales is not None:
        k_scale, v_scale = kv_scales
        if seq_sharded:
            sel_s = (jnp.arange(s_cache, dtype=jnp.int32) == slot)[None, :, None]
            k_scale = jnp.where(sel_s, k_scale_new.astype(k_scale.dtype),
                                k_scale)
            v_scale = jnp.where(sel_s, v_scale_new.astype(v_scale.dtype),
                                v_scale)
        else:
            k_scale = jax.lax.dynamic_update_slice_in_dim(
                k_scale, k_scale_new.astype(k_scale.dtype), slot, axis=1)
            v_scale = jax.lax.dynamic_update_slice_in_dim(
                v_scale, v_scale_new.astype(v_scale.dtype), slot, axis=1)
        k_scale = shd.constrain(k_scale, shd.BATCH, shd.KV_SEQ, shd.KV_HEADS)
        v_scale = shd.constrain(v_scale, shd.BATCH, shd.KV_SEQ, shd.KV_HEADS)
        new_scales = (k_scale, v_scale)
        # dequantize for the score/readout matmuls (on TPU this fuses into
        # the attention kernel; the cache traffic stays int8)
        k_att = cache_k.astype(cd) * k_scale[..., None].astype(cd)
        v_att = cache_v.astype(cd) * v_scale[..., None].astype(cd)
    else:
        k_att, v_att = cache_k, cache_v

    n_written = jnp.minimum(cache_pos + 1, s_cache)
    valid = jnp.arange(s_cache)[None, :] < n_written        # [1, S] -> broadcast
    valid = jnp.broadcast_to(valid, (b, s_cache))
    out = decode_attention(q, k_att, v_att, valid)
    out = shd.constrain(out, shd.BATCH, None, shd.HEADS, None)
    y = dense_apply(params["o"], out, cd, contract_dims=2)
    return shd.constrain(y, shd.BATCH, None, None), cache_k, cache_v, new_scales
