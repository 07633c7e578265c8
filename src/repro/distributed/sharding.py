"""Logical-axis sharding.

Every parameter and activation in the model code is annotated with *logical*
axis names; a rule table maps logical axes to physical mesh axes. The rule
table is derived per (arch, mesh) by divisibility checks, so the same model
code serves the 1-device smoke tests, the 256-chip single-pod mesh, and the
512-chip multi-pod mesh.

Parallelism scheme (DESIGN.md §4):
  * ``batch``   -> ('pod', 'data') when divisible, else 'data' — data parallel
  * ``fsdp``    -> 'data' — ZeRO-3 style parameter sharding on the non-TP dim
  * ``heads`` / ``kv_heads`` / ``mlp`` / ``vocab`` / ``experts`` / ``ssd_heads``
                -> 'model' — tensor / expert parallelism (only when divisible)
  * the ``pod`` axis is pure data parallelism: params are replicated across
    pods; gradients all-reduce over ('pod', 'data').

Archs whose head counts don't divide the model axis (hymba 25H, starcoder2
36H) fall back to replicated-attention + TP-MLP; recorded per-arch by
``sharding_profile`` and surfaced in the dry-run report.
"""
from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import MeshConfig, ModelConfig

# Logical axis vocabulary.
BATCH = "batch"          # global batch dim
SEQ = "seq"              # sequence dim (sharded only for context-parallel opt)
EMBED = "embed"          # d_model dim
FSDP = "fsdp"            # parameter dim sharded ZeRO-style over 'data'
HEADS = "heads"          # query heads
KV_HEADS = "kv_heads"    # stored KV heads (possibly repeated for divisibility)
KV_PARAM_HEADS = "kv_param_heads"  # true KV heads on params (no repeat)
KV_SEQ = "kv_seq"        # KV-cache sequence dim (context-parallel decode)
HEAD_DIM = "head_dim"
MLP = "mlp"              # d_ff dim
VOCAB = "vocab"          # vocabulary dim
EXPERTS = "experts"      # MoE expert dim
SSD_HEADS = "ssd_heads"  # mamba2/SSD head dim
SSD_STATE = "ssd_state"
LAYERS = "layers"        # stacked-layer dim (never sharded)
NULL = None


@dataclass(frozen=True)
class ShardingProfile:
    """Which TP dims are actually sharded for a given (arch, mesh)."""
    attn_tp: bool            # heads over 'model'
    mlp_tp: bool             # d_ff over 'model'
    vocab_tp: bool           # padded vocab over 'model'
    expert_tp: bool          # experts over 'model'
    ssd_tp: bool             # SSD heads over 'model'
    kv_repeat: int           # stored-KV replication factor for divisibility
    batch_axes: Tuple[str, ...]
    kv_seq_shard: bool = False  # context-parallel decode cache (seq over model)
    notes: Tuple[str, ...] = ()


# Default mesh axis for the streaming engine's slot-sharded batched fold
# (``AionConfig.slot_sharding``): window slots partition across a 1-D mesh
# with NO cross-device reduction — slots are disjoint, so each device owns
# a contiguous slot range outright (psum-free).
SLOT_AXIS = "slots"


def shard_of_window(start: float, end: float, num_shards: int) -> int:
    """Stable window -> shard assignment for the pooled sharded fold.

    The block pool places a window's blocks in per-device slot ranges at
    STAGING time — before any batch composition is known — so placement
    must be a pure function of the window identity, not of the batch.
    Both the staging shard hint and the batch executor's pooled placement
    call this, which is what keeps a window's block-table rows local to
    the shard that owns its arena range. Python's float hash is
    process-stable (PYTHONHASHSEED only perturbs str/bytes).
    """
    if num_shards <= 1:
        return 0
    return int(abs(hash((float(start), float(end))))) % num_shards


def make_slot_mesh(num_devices: int = 0,
                   axis_name: str = SLOT_AXIS) -> Optional[Mesh]:
    """1-D mesh over local devices for slot-sharded window execution.

    ``num_devices == 0`` takes every local device. Returns ``None`` when
    fewer than two devices are available — callers fall back to the
    single-device batched path, which keeps ``slot_sharding=True`` a safe
    no-op on one-device hosts (the tier-1 CPU container).
    """
    devs = jax.devices()
    n = num_devices if num_devices > 0 else len(devs)
    n = min(n, len(devs))
    if n <= 1:
        return None
    return Mesh(np.asarray(devs[:n]), (axis_name,))


def _divides(a: int, b: int) -> bool:
    return b > 0 and a > 0 and a % b == 0


def pad_vocab(vocab: int, multiple: int = 256) -> int:
    return ((vocab + multiple - 1) // multiple) * multiple


def sharding_profile(cfg: ModelConfig, mesh_cfg: MeshConfig,
                     global_batch: int, seq_len: int = 0,
                     kind: str = "train") -> ShardingProfile:
    axes = dict(zip(mesh_cfg.axes, mesh_cfg.shape))
    model = axes.get("model", 1)
    data = axes.get("data", 1)
    pod = axes.get("pod", 1)
    notes = []

    # batch: prefer ('pod','data'); drop axes that don't divide.
    batch_axes: Tuple[str, ...]
    if pod > 1 and _divides(global_batch, pod * data):
        batch_axes = ("pod", "data")
    elif _divides(global_batch, data):
        batch_axes = ("data",)
        if pod > 1:
            notes.append("batch not divisible by pod*data; pod idle on batch")
    else:
        batch_axes = ()
        notes.append(f"global_batch={global_batch} not divisible by data={data};"
                     " batch replicated (latency-bound shape)")

    attn_tp = cfg.has_attention and _divides(cfg.num_heads, model)
    if cfg.has_attention and not attn_tp:
        notes.append(f"num_heads={cfg.num_heads} % model={model} != 0: "
                     "attention is DP-only (TP-MLP hybrid fallback)")
    kv_repeat = 1
    if attn_tp:
        if _divides(cfg.num_kv_heads, model):
            kv_repeat = 1
        else:
            # repeat stored KV heads up to the model axis for divisibility
            kv_repeat = model // cfg.num_kv_heads
            if cfg.num_kv_heads * kv_repeat != model:
                # e.g. kv=3, model=16 -> no clean repeat; give up on attn TP
                attn_tp = False
                kv_repeat = 1
                notes.append("kv head repeat not integral; attention DP-only")
            else:
                notes.append(f"stored KV heads repeated x{kv_repeat} "
                             f"({cfg.num_kv_heads}->{model}) for TP divisibility")

    mlp_tp = cfg.d_ff > 0 and _divides(cfg.d_ff, model)
    vocab_tp = _divides(pad_vocab(cfg.vocab_size), model)
    expert_tp = cfg.moe.enabled and _divides(cfg.moe.num_experts, model)
    ssd_tp = False
    if cfg.ssm.enabled:
        d_inner = cfg.ssm.expand * cfg.d_model
        nheads = d_inner // cfg.ssm.head_dim
        ssd_tp = _divides(nheads, model)
        if not ssd_tp:
            notes.append(f"ssd_heads={nheads} % model={model} != 0: SSM DP-only")

    # Context-parallel decode: the decode KV cache is sequence-sharded over
    # the model axis (no head repeat — repeating stored heads inflates the
    # cache 2-16x; seq-sharding divides it by the TP degree instead, with
    # SPMD inserting the cross-shard softmax reductions). Attention *params*
    # keep their head-TP sharding; only stored-KV activations change layout.
    kv_seq_shard = False
    if kind == "decode" and cfg.has_attention:
        kv_repeat = 1
        if cfg.attn_window == 0 and model > 1 and seq_len \
                and seq_len % model == 0:
            kv_seq_shard = True
            notes.append("decode KV cache sequence-sharded over 'model' "
                         "(context-parallel decode, no KV head repeat)")

    return ShardingProfile(
        attn_tp=attn_tp, mlp_tp=mlp_tp, vocab_tp=vocab_tp,
        expert_tp=expert_tp, ssd_tp=ssd_tp, kv_repeat=kv_repeat,
        batch_axes=batch_axes, kv_seq_shard=kv_seq_shard,
        notes=tuple(notes),
    )


def make_rules(cfg: ModelConfig, mesh_cfg: MeshConfig,
               global_batch: int, seq_len: int = 0,
               kind: str = "train") -> Dict[str, Any]:
    """Logical-axis -> physical mesh axis (or None) rule table."""
    prof = sharding_profile(cfg, mesh_cfg, global_batch, seq_len, kind)
    model_size = dict(zip(mesh_cfg.axes, mesh_cfg.shape)).get("model", 1)
    kv_param_tp = prof.attn_tp and cfg.num_kv_heads % max(model_size, 1) == 0
    rules: Dict[str, Any] = {
        BATCH: prof.batch_axes if prof.batch_axes else None,
        SEQ: None,
        EMBED: None,
        FSDP: "data" if "data" in mesh_cfg.axes else None,
        HEADS: "model" if prof.attn_tp else None,
        # stored-KV head activations: head-sharded for train/prefill (via
        # repeat); unsharded for decode (the cache shards on seq instead)
        KV_HEADS: "model" if (prof.attn_tp and kind != "decode") else None,
        KV_PARAM_HEADS: "model" if kv_param_tp else None,
        KV_SEQ: "model" if prof.kv_seq_shard else None,
        HEAD_DIM: None,
        MLP: "model" if prof.mlp_tp else None,
        VOCAB: "model" if prof.vocab_tp else None,
        EXPERTS: "model" if prof.expert_tp else None,
        SSD_HEADS: "model" if prof.ssd_tp else None,
        SSD_STATE: None,
        LAYERS: None,
    }
    return rules


def logical_to_pspec(logical: Tuple[Optional[str], ...],
                     rules: Dict[str, Any]) -> P:
    phys = []
    for ax in logical:
        if ax is None:
            phys.append(None)
        else:
            phys.append(rules.get(ax))
    # trim trailing Nones for tidiness
    while phys and phys[-1] is None:
        phys.pop()
    return P(*phys)


@dataclass
class ShardCtx:
    """Ambient sharding context threaded through model code.

    ``mesh is None`` -> single-device mode: all constraints are no-ops.
    """
    mesh: Optional[Mesh]
    rules: Dict[str, Any] = field(default_factory=dict)
    profile: Optional[ShardingProfile] = None

    def pspec(self, *logical: Optional[str]) -> P:
        return logical_to_pspec(tuple(logical), self.rules)

    def sharding(self, *logical: Optional[str]) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.pspec(*logical))


_LOCAL = threading.local()


def set_ctx(ctx: Optional[ShardCtx]) -> None:
    _LOCAL.ctx = ctx


def current_ctx() -> Optional[ShardCtx]:
    return getattr(_LOCAL, "ctx", None)


@contextlib.contextmanager
def use_ctx(ctx: Optional[ShardCtx]):
    prev = current_ctx()
    set_ctx(ctx)
    try:
        yield ctx
    finally:
        set_ctx(prev)


def constrain(x, *logical: Optional[str]):
    """``with_sharding_constraint`` by logical axes; no-op without a mesh."""
    ctx = current_ctx()
    if ctx is None or ctx.mesh is None:
        return x
    spec = ctx.pspec(*logical)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def tree_pspecs(spec_tree):
    """Map a tree of logical-axis tuples to PartitionSpecs via the ambient
    context (identity P() tree when no mesh)."""
    ctx = current_ctx()
    rules = ctx.rules if ctx is not None else {}
    return jax.tree.map(
        lambda logical: logical_to_pspec(logical, rules),
        spec_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )
