"""Mixture-of-experts FFN with expert parallelism.

Routing (softmax top-k + aux losses) runs in plain SPMD land. The expert
FFN runs inside ``shard_map``: experts are sharded over the ``model`` mesh
axis while tokens stay batch-sharded over ``data`` (replicated over
``model``), so each device gathers *locally* the top-capacity tokens for its
local experts, applies the FFN, scatter-adds into a partial output, and the
partials are ``psum``-ed over ``model``. This replaces the classic
all-to-all with one all-reduce of the combined output — no token tensors are
ever all-gathered.

Capacity semantics follow GShard/Switch: per expert, at most
``ceil(T·top_k·cf/E)`` tokens are kept (by routing weight); overflow tokens
contribute nothing (their residual passes through).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import sharding as shd
from repro.models.layers import dense_init


def moe_init(key, cfg: ModelConfig, dtype):
    d, f, e = cfg.d_model, cfg.d_ff, cfg.moe.num_experts
    ks = jax.random.split(key, 4)
    router_p, router_s = dense_init(ks[0], d, (e,), (shd.FSDP, None),
                                    jnp.float32)
    scale = 1.0 / math.sqrt(d)
    wg = jax.random.normal(ks[1], (e, d, f), dtype=dtype) * scale
    wu = jax.random.normal(ks[2], (e, d, f), dtype=dtype) * scale
    wd = jax.random.normal(ks[3], (e, f, d), dtype=dtype) / math.sqrt(f)
    params = {"router": router_p, "wg": wg, "wu": wu, "wd": wd}
    specs = {
        "router": router_s,
        "wg": (shd.EXPERTS, shd.FSDP, None),
        "wu": (shd.EXPERTS, shd.FSDP, None),
        "wd": (shd.EXPERTS, None, shd.FSDP),
    }
    return params, specs


def _capacity(tokens: int, cfg: ModelConfig) -> int:
    e, k, cf = cfg.moe.num_experts, cfg.moe.top_k, cfg.moe.capacity_factor
    c = int(math.ceil(tokens * k * cf / e))
    c = ((c + 63) // 64) * 64                      # lane-align
    return min(max(c, 64), tokens)


def _expert_ffn(x_flat, idx, wts, wg, wu, wd, e_offset, capacity, variant):
    """Local expert compute. x_flat [T, D]; idx/wts [T, K];
    wg/wu/wd [E_loc, ...]. Returns partial output [T, D]."""
    e_loc = wg.shape[0]
    t, d = x_flat.shape
    eids = e_offset + jnp.arange(e_loc, dtype=idx.dtype)
    hit = idx[None, :, :] == eids[:, None, None]              # [E_loc, T, K]
    aff = jnp.sum(jnp.where(hit, wts[None], 0.0), axis=-1)    # [E_loc, T]
    gate, token_ids = jax.lax.top_k(aff, capacity)            # [E_loc, C]
    xg = jnp.take(x_flat, token_ids.reshape(-1), axis=0)
    xg = xg.reshape(e_loc, capacity, d)                       # [E_loc, C, D]
    if variant == "swiglu":
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, wg)) \
            * jnp.einsum("ecd,edf->ecf", xg, wu)
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xg, wu))
    y = jnp.einsum("ecf,efd->ecd", h, wd)
    y = y * gate[..., None].astype(y.dtype)
    out = jnp.zeros((t, d), dtype=y.dtype)
    out = out.at[token_ids.reshape(-1)].add(y.reshape(-1, d))
    return out


def moe_forward(params, x, cfg: ModelConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, D] -> (y [B, S, D], aux_loss scalar fp32)."""
    cd = x.dtype
    b, s, d = x.shape
    e, k = cfg.moe.num_experts, cfg.moe.top_k

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"]["w"])                # fp32
    probs = jax.nn.softmax(logits, axis=-1)
    wts, idx = jax.lax.top_k(probs, k)                        # [B,S,K]
    wts = wts / jnp.maximum(jnp.sum(wts, axis=-1, keepdims=True), 1e-9)

    # Switch aux losses
    me = jnp.mean(probs, axis=(0, 1))                         # [E]
    ce_frac = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=2),
        axis=(0, 1))                                          # [E]
    aux = cfg.moe.router_aux_weight * e * jnp.sum(me * ce_frac)
    zloss = cfg.moe.router_z_weight * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    aux = aux + zloss

    x_flat = x.reshape(b * s, d)
    idx_flat = idx.reshape(b * s, k)
    wts_flat = wts.reshape(b * s, k).astype(cd)

    ctx = shd.current_ctx()
    expert_tp = (ctx is not None and ctx.mesh is not None
                 and ctx.profile is not None and ctx.profile.expert_tp)
    if not expert_tp:
        capacity = _capacity(b * s, cfg)
        y = _expert_ffn(x_flat, idx_flat, wts_flat,
                        params["wg"].astype(cd), params["wu"].astype(cd),
                        params["wd"].astype(cd), 0, capacity, cfg.mlp_variant)
        return y.reshape(b, s, d), aux

    mesh = ctx.mesh
    batch_axes = ctx.profile.batch_axes
    n_model = mesh.shape["model"]
    e_loc = e // n_model
    # local token count after batch sharding
    n_batch = 1
    for ax in batch_axes:
        n_batch *= mesh.shape[ax]
    t_loc = (b // max(n_batch, 1)) * s
    capacity = _capacity(t_loc, cfg)

    bspec = P(batch_axes if batch_axes else None, None)

    def shard_fn(xf, idxf, wtsf, wg, wu, wd):
        e_off = jax.lax.axis_index("model") * e_loc
        out = _expert_ffn(xf, idxf, wtsf, wg, wu, wd, e_off,
                          capacity, cfg.mlp_variant)
        return jax.lax.psum(out, axis_name="model")

    y = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(bspec, bspec, bspec,
                  P("model", None, None), P("model", None, None),
                  P("model", None, None)),
        out_specs=bspec,
        check_vma=False,
    )(x_flat, idx_flat, wts_flat,
      params["wg"].astype(cd), params["wu"].astype(cd),
      params["wd"].astype(cd))
    return y.reshape(b, s, d), aux
