"""The engine's Pallas folds compile for a TPU v5e at the shapes the chip runs.

The TPU compiler is installed even where no chip is attached: a described
v5e topology lets these tests compile each fold for the real chip, which
catches what interpret mode cannot — block shapes that break the (8, 128)
tiling, an arena layout that forces a relayout copy per fold, more VMEM
than a kernel may use. Each compile takes a second or two. The topology
is described inside a fixture, never at import: only one process may load
the TPU library at a time, and every xdist worker imports this file. The
tests share one xdist group, so ``--dist loadfile`` and ``--dist
loadgroup`` both keep them on one worker. A topology that cannot be
described fails the tests: the TPU compiler is part of the installation.
"""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.kernels import (
    segment_aggregate_batched, segment_aggregate_block_table,
    segment_aggregate_block_table_splitk,
)

# the Table-1 stock-market deployment the on-chip smoke runs: 128 symbols,
# 1664-byte events (416 f32 lanes), 512-event blocks, a 2560-slot arena
POOL, CAP, STOCK_W, KEYS = 2560, 512, 416, 128
NUM_SLOTS = 8
ARENA_BYTES = POOL * CAP * STOCK_W * 4

pytestmark = pytest.mark.xdist_group("tpu_compile")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip can be written to the persistent
    # cache but never read back: keep the cache out of these compiles
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args, **kw):
    compiled = fn.lower(*args, **kw).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text          # the Pallas kernel is there
    return compiled


def _block_table_args(sharding, rows):
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (s((POOL, CAP, STOCK_W)), s((rows, CAP), jnp.int32),
            s((rows,), jnp.int32)), dict(
        valid=s((rows, CAP), jnp.bool_), slot_ids=s((rows,), jnp.int32))


def test_block_table_fold_compiles_for_v5e(one_chip):
    """Stock stats over the pool arena: the fold reads the arena in the
    chip's own layout for it — no relayout copy of the 2 GiB arena."""
    args, kw = _block_table_args(one_chip, 256)
    compiled = _compile(segment_aggregate_block_table, *args, KEYS,
                        num_slots=NUM_SLOTS, backend="pallas", num_cols=1,
                        **kw)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < ARENA_BYTES // 100, mem


def test_splitk_fold_compiles_for_v5e(one_chip):
    """The largest split-K launch group the executor dispatches: 8 chunks
    of 128 rows, one partial accumulator per chunk."""
    args, kw = _block_table_args(one_chip, 8 * 128)
    compiled = _compile(segment_aggregate_block_table_splitk, *args, KEYS,
                        128, num_slots=NUM_SLOTS, backend="pallas",
                        num_cols=1, **kw)
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < ARENA_BYTES // 100, mem


def test_flat_fold_compiles_for_v5e(one_chip):
    """The flat-grid fold at Linear Road's shapes (256 segments, speed and
    stopped-flag columns): lrb's batched fold and the stacked fallback."""
    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    rows = 64
    _compile(segment_aggregate_batched, s((rows, CAP, 2)),
             s((rows, CAP), jnp.int32), 256,
             valid=s((rows, CAP), jnp.bool_),
             slot_ids=s((rows,), jnp.int32), num_slots=NUM_SLOTS,
             backend="pallas", stats=("sum", "count"))


def test_slot_sharded_fold_compiles_for_four_v5e_chips(topo, one_chip):
    """The slot-sharded split-K fold over a 2x2 mesh: each chip holds a
    quarter of the arena and folds it with no collective on the arena."""
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("slots",))
    rows = 4 * 256
    arena = NamedSharding(mesh, P("slots", None, None))
    by_row = NamedSharding(mesh, P("slots"))
    by_row2 = NamedSharding(mesh, P("slots", None))
    compiled = _compile(
        segment_aggregate_block_table_splitk,
        jax.ShapeDtypeStruct((POOL, CAP, STOCK_W), jnp.float32,
                             sharding=arena),
        jax.ShapeDtypeStruct((rows, CAP), jnp.int32, sharding=by_row2),
        jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=by_row),
        KEYS, 128,
        valid=jax.ShapeDtypeStruct((rows, CAP), jnp.bool_,
                                   sharding=by_row2),
        slot_ids=jax.ShapeDtypeStruct((rows,), jnp.int32, sharding=by_row),
        num_slots=4 * NUM_SLOTS, backend="pallas", mesh=mesh, num_cols=1)
    mem = compiled.memory_analysis()
    # per-device figures: a quarter of the arena, never the whole of it
    assert mem.argument_size_in_bytes < ARENA_BYTES // 2, mem
    assert mem.temp_size_in_bytes < ARENA_BYTES // 100, mem
