#!/usr/bin/env python3
"""Records ``fixtures/fixture.xplane.pb``, the small trace that the trace
reduction's test reads. Run on a TPU from the checkout's root:

    python3 benchmarks/aion/fixtures/record_fixture_trace.py

Two annotated ``bench.step`` spans, each folding 256 arena rows through
the stock operator's Pallas block-table split-K fold and the Linear Road
operator's take-then-flat Pallas fold, with 50 ms of host sleep between
them for an idle gap.
"""
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[2] / "src"))


def main():
    import jax
    import jax.numpy as jnp
    from repro.core.operators import make_operator
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_fixture_trace: needs a TPU")
    slots, cap, rows = 512, 512, 256
    stock = make_operator("stock", cap, 416, num_keys=128)
    lrb = make_operator("lrb", cap, 384, num_segments=256)
    key = jax.random.PRNGKey(0)
    keys = jax.random.randint(key, (slots, cap), 0, 1 << 20, jnp.int32)
    arena_s = jax.random.uniform(key, (slots, cap, 416), jnp.float32)
    arena_l = jax.random.uniform(key, (slots, cap, 384), jnp.float32)
    table = jnp.arange(rows, dtype=jnp.int32) * 2
    fills = jnp.full((rows,), cap, jnp.int32)
    owner = jnp.arange(rows, dtype=jnp.int32) % 4

    def fold():
        a = stock.fold_batch({"keys": keys, "values": arena_s}, fills,
                             owner, 4, table=table, splitk=128)
        b = lrb.fold_batch({"keys": keys, "values": arena_l}, fills,
                           owner, 4, table=table)
        jax.block_until_ready((a, b))

    fold()                                  # compile outside the trace
    out = HERE / ".fixture_trace"
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(out), profiler_options=opts)
    for _ in range(2):
        with jax.profiler.TraceAnnotation("bench.step"):
            with jax.profiler.TraceAnnotation("bench.poll"):
                fold()
            with jax.profiler.TraceAnnotation("bench.ingest"):
                time.sleep(0.05)
    jax.profiler.stop_trace()
    found = sorted(out.glob("plugins/profile/*/*.xplane.pb"))
    shutil.copy(found[-1], HERE / "fixture.xplane.pb")
    shutil.rmtree(out, ignore_errors=True)
    print("wrote", HERE / "fixture.xplane.pb",
          (HERE / "fixture.xplane.pb").stat().st_size, "B")


if __name__ == "__main__":
    main()
