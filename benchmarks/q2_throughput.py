"""Q2 (paper Figs. 3-5): AION's ingestion/processing-rate overhead vs the
in-memory baseline when everything fits in memory.

Also benchmarks the batched multi-window execution path
(``fold_benchmark``): with many concurrent due windows, folding them in
one device pass vs one ``execute_window`` per window — and, with
``--devices N``, the slot-sharded multi-device fold vs the single-device
batched fold on N simulated CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=N``, which must be
set before jax imports: repro imports here are function-local so the
``__main__`` argparse can set it first)."""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

EVENTS_PER_WM = 1500
N_WATERMARKS = 8


def run_one(workload, baseline: bool, include_late: bool) -> Dict:
    from repro.configs.base import AionConfig
    from repro.core import InMemoryPolicy, StreamEngine, TumblingWindows
    from repro.core.operators import make_operator
    from repro.core.triggers import DeltaTTrigger
    from repro.data.generators import make_generator
    gen = make_generator(workload, seed=3)
    aion = AionConfig(block_size=1024)
    kw = {}
    if workload.operator == "stock":
        kw = {"num_keys": workload.num_keys}
    elif workload.operator == "lrb":
        kw = {"num_segments": workload.num_keys}
    elif workload.operator == "bigrams":
        kw = {"vocab": 64}
    op = make_operator(workload.operator, aion.block_size, gen.width, **kw)
    eng = StreamEngine(
        assigner=TumblingWindows(workload.window_duration),
        operator=op, aion=aion, value_width=gen.width,
        device_budget_bytes=512 << 20,       # fits fully in memory (Q2)
        policy=InMemoryPolicy() if baseline else None,
        trigger=DeltaTTrigger(executions=1),
    )
    wd = workload.window_duration
    now = 4 * wd
    ingested = 0
    # warmup
    eng.ingest(gen.batch(200, now), now)
    eng.advance_watermark(now, now)
    t0 = time.time()
    for _ in range(N_WATERMARKS):
        batch = gen.batch(EVENTS_PER_WM, now)
        if not include_late:
            batch = batch.select(batch.timestamps >= now - wd)
        eng.ingest(batch, now)
        ingested += len(batch)
        eng.advance_watermark(now, now)
        eng.poll(now)
        now += wd
    eng.io.drain()
    dt = time.time() - t0
    eng.close()
    return {
        "workload": workload.name,
        "backend": "baseline" if baseline else "aion",
        "late_included": include_late,
        "events_per_sec": ingested / dt,
        "processed_windows": eng.metrics.live_executions
        + eng.metrics.late_executions,
        "fetch_stall_s": round(eng.metrics.fetch_stall_seconds, 4),
        "batch_occupancy": round(eng.metrics.mean_batch_occupancy, 2),
        "dispatch_s_per_exec": round(
            eng.metrics.dispatch_seconds_per_execution, 6),
    }


def fold_benchmark(num_windows: int = 8, events_per_window: int = 2000,
                   repeats: int = 5,
                   modes: tuple = (("batched", True, False),
                                   ("per_window", False, False)),
                   op_name: str = "average",
                   num_keys: int = 256) -> Dict:
    """Fold throughput with ``num_windows`` concurrent due windows:
    batched single-pass execution vs the per-window reference path.
    Reports events folded per second of execution wall time, batch
    occupancy, and host seconds dispatching the fold per window
    execution.

    ``modes`` rows are ``(label, batched_execution, slot_sharding)`` —
    the ``--devices N`` sweep adds a slot-sharded mode that partitions
    the batch's window slots across the simulated device mesh. The fold
    cost of the keyed operators (``stock``/``lrb``) scales with
    ``num_slots * num_keys`` (the one-hot segment axis), which is the
    regime slot sharding targets: each device reduces a D-times smaller
    row block onto a D-times narrower slot range.
    """
    from repro.configs.base import AionConfig
    from repro.core import StreamEngine, TumblingWindows
    from repro.core.events import EventBatch
    from repro.core.operators import make_operator
    from repro.core.triggers import DeltaTTrigger

    wd = 10.0
    horizon = num_windows * wd
    out: Dict[str, Dict] = {}
    op_kw = {}
    if op_name == "stock":
        op_kw = {"num_keys": num_keys}
    elif op_name == "lrb":
        op_kw = {"num_segments": num_keys}
    for label, batched, sharded in modes:
        aion = AionConfig(block_size=1024, batched_execution=batched,
                          slot_sharding=sharded)
        op = make_operator(op_name, aion.block_size, 1, **op_kw)
        eng = StreamEngine(
            assigner=TumblingWindows(wd), operator=op, aion=aion,
            value_width=1, device_budget_bytes=512 << 20,
            trigger=DeltaTTrigger(executions=1),
        )
        rng = np.random.default_rng(0)
        n = num_windows * events_per_window

        def round_events(r):
            # exactly events_per_window per window: the fold shapes are
            # identical every round, so the numbers reflect steady-state
            # fold throughput rather than one-off jit compiles
            base = r * horizon
            ts = np.concatenate([
                rng.uniform(base + i * wd, base + (i + 1) * wd,
                            events_per_window)
                for i in range(num_windows)])
            return EventBatch(
                rng.integers(0, 64, n).astype(np.int32), ts,
                rng.normal(size=(n, 1)).astype(np.float32))

        # warmup round compiles the fold(s); reset counters so reported
        # dispatch time reflects steady state, not compilation
        eng.ingest(round_events(0), now=0.0)
        eng.advance_watermark(horizon, now=horizon)
        m = eng.metrics
        m.live_executions = 0
        m.batch_executions = 0
        m.batched_windows = 0
        m.sharded_batch_executions = 0
        m.batch_dispatch_seconds = 0.0
        m.batch_occupancy_series.clear()
        times = []
        for r in range(1, repeats + 1):
            eng.ingest(round_events(r), now=r * horizon)
            t0 = time.time()
            # all num_windows windows of this round expire at once
            eng.advance_watermark((r + 1) * horizon, now=(r + 1) * horizon)
            times.append(time.time() - t0)
        eng.io.drain()
        out[label] = {
            "fold_events_per_sec": n * repeats / sum(times),
            "exec_wall_s": round(sum(times), 4),
            "windows_executed": m.live_executions,
            "batch_occupancy": round(m.mean_batch_occupancy, 2),
            "dispatch_s_per_exec": round(
                m.dispatch_seconds_per_execution, 6),
            "sharded_passes": m.sharded_batch_executions,
        }
        eng.close()
    if "batched" in out and "per_window" in out:
        out["speedup"] = round(
            out["batched"]["fold_events_per_sec"]
            / max(out["per_window"]["fold_events_per_sec"], 1e-9), 2)
    out["num_windows"] = num_windows
    return out


def gather_benchmark(num_windows: int = 8, events_per_window: int = 8000,
                     repeats: int = 20, warmup: int = 3,
                     op_name: str = "lrb", num_keys: int = 64,
                     emit_json: str = "BENCH_q2_gather.json") -> Dict:
    """Gather vs fold seconds for the batched execution path: the
    persistent block pool (block tables, zero-copy) vs the device-concat
    baseline, at ``num_windows`` concurrent due windows.

    Two scenarios:
      * **hot** — everything device-resident (InMemoryPolicy pins blocks,
        so pooled rows never leave the arena between re-executions): the
        pooled gather is a table of Python ints + one take inside the
        fold, the baseline re-stacks every row every batch.
      * **cold** — spill pressure with a simulated persistent tier
        (LocalRhoMinPolicy keeps a rho_min=0.5 bootstrap resident, the
        rest destages after every execution and every re-read pays the
        simulated persistent-tier cost): the pooled path demand-fills
        the cold half at PRIO_DEMAND_STAGE and hides that I/O behind the
        fold of the resident half (stall = what the fold could not
        hide), the baseline pays the same reads synchronously inside the
        gather.

    Reported per mode: gather seconds (batch assembly outside the fold
    call — ``EngineMetrics.batch_gather_seconds``), fold seconds, overlap
    stall, end-to-end fold throughput. The acceptance bar is
    ``hot.gather_speedup >= 3`` at >= 8 due windows; results land in
    ``emit_json`` (checked in as BENCH_q2_gather.json).
    """
    import json

    from repro.configs.base import AionConfig
    from repro.core import InMemoryPolicy, StreamEngine, TumblingWindows
    from repro.core.batch_exec import BatchWorkItem
    from repro.core.events import EventBatch
    from repro.core.operators import make_operator
    from repro.core.policies import LocalRhoMinPolicy
    from repro.core.triggers import DeltaTTrigger

    wd = 10.0
    horizon = num_windows * wd
    n = num_windows * events_per_window
    op_kw = {}
    if op_name == "stock":
        op_kw = {"num_keys": num_keys}
    elif op_name == "lrb":
        op_kw = {"num_segments": num_keys}

    def drive(pooled: bool, hot: bool) -> Dict:
        aion = AionConfig(block_size=1024, batched_execution=True,
                          block_pool=pooled)
        op = make_operator(op_name, aion.block_size, 1, **op_kw)
        eng = StreamEngine(
            assigner=TumblingWindows(wd), operator=op, aion=aion,
            value_width=1, device_budget_bytes=512 << 20,
            # hot: everything stays resident between re-executions;
            # cold: half the blocks destage after every execution
            # (rho_min bootstrap keeps the other half) and persistent-
            # tier reads cost ~0.8 ms/block (simulated)
            policy=InMemoryPolicy() if hot
            else LocalRhoMinPolicy(rho_min=0.5, tau=1e9),
            simulated_seconds_per_byte=0.0 if hot else 5e-8,
            trigger=DeltaTTrigger(executions=1),
        )
        rng = np.random.default_rng(0)
        ts = np.concatenate([
            rng.uniform(i * wd, (i + 1) * wd, events_per_window)
            for i in range(num_windows)])
        eng.ingest(EventBatch(rng.integers(0, num_keys, n).astype(np.int32),
                              ts, rng.normal(size=(n, 1)).astype(np.float32)),
                   now=0.0)
        eng.advance_watermark(horizon, now=horizon)      # live batch+compile
        eng.io.drain()

        def late_batch(r):
            items = [BatchWorkItem(wid, eng.windows[wid], True)
                     for wid in sorted(eng.windows)]
            eng.batch_exec.execute(items, now=horizon + 1.0 + r)
            if not hot:
                eng.io.drain()                  # let destage make it cold
        # warmup rounds compile every fold/gather variant of the late
        # path; reset counters so the measurement is steady state
        for r in range(warmup):
            late_batch(r - warmup)
        m = eng.metrics
        m.batch_gather_seconds = 0.0
        m.batch_dispatch_seconds = 0.0
        m.batch_stall_seconds = 0.0
        m.pooled_rows = m.fallback_rows = m.demand_pool_fills = 0
        # steady state: re-execute the same due set repeatedly (the
        # batched late path — a pure function of bucket contents)
        t0 = time.time()
        for r in range(repeats):
            late_batch(r)
        wall = time.time() - t0
        out = {
            "gather_s": round(m.batch_gather_seconds, 6),
            "fold_s": round(m.batch_dispatch_seconds, 6),
            "stall_s": round(m.batch_stall_seconds, 6),
            "wall_s": round(wall, 6),
            "fold_events_per_sec": round(n * repeats / max(wall, 1e-9)),
            "pooled_rows": m.pooled_rows,
            "fallback_rows": m.fallback_rows,
            "demand_pool_fills": m.demand_pool_fills,
        }
        eng.close()
        return out

    out: Dict = {"num_windows": num_windows,
                 "events_per_window": events_per_window,
                 "repeats": repeats, "workload": op_name}
    for scen, hot in (("hot", True), ("cold", False)):
        pooled = drive(True, hot)
        concat = drive(False, hot)
        out[scen] = {
            "pooled": pooled, "device_concat": concat,
            "gather_speedup": round(
                concat["gather_s"] / max(pooled["gather_s"], 1e-9), 2),
            "throughput_ratio": round(
                pooled["fold_events_per_sec"]
                / max(concat["fold_events_per_sec"], 1e-9), 3),
        }
    if emit_json:
        with open(emit_json, "w") as f:
            json.dump(out, f, indent=2)
    return out


def pipeline_benchmark(num_windows: int = 8, num_rounds: int = 10,
                       events_per_window: int = 4000,
                       sim_spb: float = 8e-7, op_name: str = "lrb",
                       num_keys: int = 256,
                       emit_json: str = "BENCH_q2_gather.json") -> Dict:
    """Pipelined async engine vs the synchronous loop (ISSUE 6
    tentpole): ``num_rounds`` independent groups of ``num_windows`` due
    windows, every p-block cold (destaged to a simulated persistent
    tier), executed end-to-end.

    The synchronous loop pays, per round, demand staging then the fold,
    serially across rounds. The pipelined engine submits every round to
    the fold worker up front: round k+1's staging (prefetch at
    PRIO_STAGE, promoted to PRIO_DEMAND_STAGE when its fold starts)
    overlaps round k's fold, so the end-to-end wall converges to
    max(total I/O, total fold) + one pipeline fill. ``sim_spb`` is tuned
    so staging a round costs about as much as folding it — the regime
    the overlap targets. Acceptance: ``pipeline_vs_sync >= 1.3`` at 8
    due windows; the result merges into ``emit_json``.
    """
    import json
    import os

    from repro.configs.base import AionConfig
    from repro.core import InMemoryPolicy, StreamEngine, TumblingWindows
    from repro.core.batch_exec import BatchWorkItem
    from repro.core.events import EventBatch
    from repro.core.operators import make_operator
    from repro.core.triggers import DeltaTTrigger

    wd = 10.0
    op_kw = {}
    if op_name == "stock":
        op_kw = {"num_keys": num_keys}
    elif op_name == "lrb":
        op_kw = {"num_segments": num_keys}

    def build(pipelined: bool) -> "StreamEngine":
        aion = AionConfig(block_size=1024, batched_execution=True,
                          block_pool=True,
                          pipelined_execution=pipelined)
        op = make_operator(op_name, aion.block_size, 1, **op_kw)
        return StreamEngine(
            assigner=TumblingWindows(wd), operator=op, aion=aion,
            value_width=1, device_budget_bytes=512 << 20,
            policy=InMemoryPolicy(),     # no post-execute destage noise
            simulated_seconds_per_byte=sim_spb,
            trigger=DeltaTTrigger(executions=1),
        )

    def rounds_of(eng):
        """Ingest num_rounds disjoint window groups; returns the groups
        (identical shapes round-over-round: one jit compile)."""
        rng = np.random.default_rng(0)
        n = num_windows * events_per_window
        for r in range(num_rounds):
            base = r * num_windows * wd
            ts = np.concatenate([
                rng.uniform(base + i * wd, base + (i + 1) * wd,
                            events_per_window)
                for i in range(num_windows)])
            eng.ingest(
                EventBatch(rng.integers(0, num_keys, n).astype(np.int32),
                           ts, rng.normal(size=(n, 1)).astype(np.float32)),
                now=0.0)
        wids = sorted(eng.windows)
        assert len(wids) == num_rounds * num_windows
        return [[BatchWorkItem(wid, eng.windows[wid], True)
                 for wid in wids[r * num_windows:(r + 1) * num_windows]]
                for r in range(num_rounds)]

    def make_cold(eng, items):
        for it in items:
            for blk in list(it.state.blocks):
                eng.io.destage_block_sync(blk)

    def drive(pipelined: bool) -> float:
        eng = build(pipelined)
        rounds = rounds_of(eng)
        # warmup: compile the cold-path fold on round 0's group, then
        # re-destage it so the measured run starts fully cold
        make_cold(eng, rounds[0])
        eng.batch_exec.execute(rounds[0], now=1.0)
        for items in rounds:
            make_cold(eng, items)
        assert eng.io.drain(timeout=120)
        t0 = time.time()
        if pipelined:
            for r, items in enumerate(rounds):
                eng._submit_round(items, now=2.0 + r)
            assert eng.pipeline.drain(timeout=300)
        else:
            for r, items in enumerate(rounds):
                eng.batch_exec.execute(items, now=2.0 + r)
        wall = time.time() - t0
        assert eng.observability()["io"]["errors"] == 0
        eng.close()
        return wall

    sync_wall = drive(False)
    pipe_wall = drive(True)
    out = {
        "num_windows": num_windows, "num_rounds": num_rounds,
        "events_per_window": events_per_window, "workload": op_name,
        "sim_seconds_per_byte": sim_spb,
        "sync_wall_s": round(sync_wall, 4),
        "pipelined_wall_s": round(pipe_wall, 4),
        "pipeline_vs_sync": round(sync_wall / max(pipe_wall, 1e-9), 2),
    }
    if emit_json:
        merged = {}
        if os.path.exists(emit_json):
            with open(emit_json) as f:
                merged = json.load(f)
        merged["pipeline"] = out
        with open(emit_json, "w") as f:
            json.dump(merged, f, indent=2)
    return out


def skew_benchmark(num_windows: int = 8, rounds: int = 10,
                   chunk: int = 16, zipf_a: float = 1.4,
                   op_name: str = "stock", num_keys: int = 256,
                   emit_json: str = "BENCH_q2_gather.json") -> Dict:
    """Split-K chunked fold vs the stripe fold on a Zipf-skewed,
    growing-late-table workload (ISSUE 8 tentpole).

    ``num_windows`` due windows whose block tables grow every round —
    late waves dealt across windows by Zipf(``zipf_a``) weights, so one
    hot window owns most rows — then the whole due set re-executes
    (the batched late path). The stripe fold pads the table to the next
    power of two (up to 2x wasted rows) and re-jits every time growth
    crosses a pow2 boundary; the split-K fold decomposes every round
    into {1,2,4,8} x ``chunk``-row launch groups, so after one warmup
    every shape is cached: **zero recompiles as the batch grows** and
    padding bounded by chunk-1 rows.

    Reported per mode: fold seconds, fold row-throughput, recompiles
    during the measured rounds (jit cache-size delta on the operator's
    ``fold_batch``), padded-vs-real row ratio. Acceptance:
    ``splitk_vs_stripe >= 1.5`` at 8 due windows with
    ``recompiles == 0`` on the split-K side; the section merges into
    ``emit_json``."""
    import json
    import os

    from repro.configs.base import AionConfig
    from repro.core import InMemoryPolicy, StreamEngine, TumblingWindows
    from repro.core.batch_exec import BatchWorkItem
    from repro.core.events import EventBatch
    from repro.core.operators import make_operator
    from repro.core.triggers import DeltaTTrigger

    wd = 10.0
    horizon = num_windows * wd
    bs = 256
    # both modes warm at 15*chunk rows (the split-K side needs one round
    # that decomposes 8+4+2+1 to cache every launch shape); measured
    # rounds then grow THROUGH the 256 and 512 pow2 boundaries, so the
    # stripe fold re-jits mid-run and pads up to ~2x, while every
    # split-K decomposition reuses the warmed {1,2,4,8}*chunk shapes
    warm_rows = 15 * chunk
    row_targets = [250, 270, 300, 340, 390, 450, 510, 580, 660, 750,
                   850, 960][:rounds]
    weights = 1.0 / np.arange(1, num_windows + 1) ** zipf_a
    weights /= weights.sum()

    def drive(splitk: int) -> Dict:
        aion = AionConfig(block_size=bs, batched_execution=True,
                          block_pool=True, pool_slots=2048,
                          splitk_chunk_rows=splitk)
        op = make_operator(op_name, bs, 1, num_keys=num_keys)
        eng = StreamEngine(
            assigner=TumblingWindows(wd), operator=op, aion=aion,
            value_width=1, device_budget_bytes=512 << 20,
            policy=InMemoryPolicy(),      # hot arena: fold-bound
            trigger=DeltaTTrigger(executions=1),
        )
        rng = np.random.default_rng(0)

        def grow_to(target_rows: int, have: np.ndarray):
            """Late wave in whole blocks, dealt by Zipf weights."""
            want = np.floor(weights * target_rows).astype(int)
            want[0] += target_rows - want.sum()        # hot window
            delta = np.maximum(want - have, 0)
            parts = []
            for i, d in enumerate(delta):
                if d == 0:
                    continue
                n = d * bs                  # whole blocks: rows == n/bs
                parts.append(EventBatch(
                    rng.integers(0, num_keys, n).astype(np.int32),
                    rng.uniform(i * wd, (i + 1) * wd, n),
                    rng.normal(size=(n, 1)).astype(np.float32)))
            for b in parts:
                eng.ingest(b, now=horizon + 1.0)
            return have + delta

        def late_batch(r):
            items = [BatchWorkItem(wid, eng.windows[wid], True)
                     for wid in sorted(eng.windows)]
            eng.batch_exec.execute(items, now=horizon + 2.0 + r)

        have = np.zeros(num_windows, int)
        have = grow_to(warm_rows, have)
        eng.advance_watermark(horizon, now=horizon)    # live + compile
        eng.io.drain()
        late_batch(-1)                                 # warm the late path
        m = eng.metrics
        cache0 = eng.observability()["fold"]["cache_size"]
        m.batch_dispatch_seconds = 0.0
        m.pooled_rows = 0
        launches0 = m.splitk_launches
        rows_folded = 0
        t0 = time.time()
        for r, target in enumerate(row_targets):
            have = grow_to(max(target, int(have.sum())), have)
            late_batch(r)
            rows_folded += int(have.sum())
        wall = time.time() - t0
        out = {
            "fold_s": round(m.batch_dispatch_seconds, 6),
            "wall_s": round(wall, 6),
            "rows_folded": rows_folded,
            "fold_rows_per_sec": round(
                rows_folded / max(m.batch_dispatch_seconds, 1e-9)),
            "recompiles": eng.observability()["fold"]["cache_size"]
            - cache0,
            "splitk_launches": m.splitk_launches - launches0,
        }
        eng.close()
        return out

    stripe = drive(0)
    splitk_out = drive(chunk)
    out: Dict = {
        "num_windows": num_windows, "rounds": len(row_targets),
        "block_size": bs, "chunk_rows": chunk, "zipf_a": zipf_a,
        "workload": op_name, "num_keys": num_keys,
        "hot_window_share": round(float(weights[0]), 3),
        "stripe": stripe, "splitk": splitk_out,
        "splitk_vs_stripe": round(
            splitk_out["fold_rows_per_sec"]
            / max(stripe["fold_rows_per_sec"], 1e-9), 2),
    }
    if emit_json:
        merged = {}
        if os.path.exists(emit_json):
            with open(emit_json) as f:
                merged = json.load(f)
        merged["splitk_vs_stripe"] = out
        with open(emit_json, "w") as f:
            json.dump(merged, f, indent=2)
    return out


def obs_overhead_benchmark(num_windows: int = 8, rounds: int = 40,
                           events_per_window: int = 4000,
                           op_name: str = "average",
                           emit_json: str = "BENCH_q2_gather.json"
                           ) -> Dict:
    """Tracing-overhead probe (ISSUE 10): the SAME fold-bound late
    re-execution drive at ``trace_sample_rate`` 0.0 vs 1.0.

    Each measured round folds every window's hot (arena-resident) block
    table under a root span — at rate 1.0 every fold-round span, its
    attrs and the ring-buffer append are live; at 0.0 the tracer hands
    out ``NULL_SPAN`` and the instrumented path must cost nothing.
    Acceptance (ISSUE 10): wall overhead at rate 1.0 under 5%. The
    section merges into ``emit_json`` as ``tracing_overhead``."""
    import json
    import os

    from repro.configs.base import AionConfig
    from repro.core import InMemoryPolicy, StreamEngine, TumblingWindows
    from repro.core.batch_exec import BatchWorkItem
    from repro.core.events import EventBatch
    from repro.core.operators import make_operator
    from repro.core.triggers import DeltaTTrigger

    wd = 10.0
    bs = 256
    horizon = num_windows * wd

    def drive(rate: float) -> Dict:
        aion = AionConfig(block_size=bs, batched_execution=True,
                          block_pool=True, pool_slots=2048,
                          trace_sample_rate=rate)
        op = make_operator(op_name, bs, 1)
        eng = StreamEngine(
            assigner=TumblingWindows(wd), operator=op, aion=aion,
            value_width=1, device_budget_bytes=256 << 20,
            policy=InMemoryPolicy(),      # hot arena: fold-bound
            trigger=DeltaTTrigger(executions=1))
        rng = np.random.default_rng(0)
        for i in range(num_windows):
            n = events_per_window
            eng.ingest(EventBatch(
                rng.integers(0, 64, n).astype(np.int32),
                rng.uniform(i * wd, (i + 1) * wd, n),
                rng.normal(size=(n, 1)).astype(np.float32)), now=0.5)
        eng.advance_watermark(horizon, now=horizon)    # live + compile

        def late_items():
            return [BatchWorkItem(wid, eng.windows[wid], True)
                    for wid in sorted(eng.windows)]
        eng.batch_exec.execute(late_items(), now=horizon + 1.0)  # warm
        rows = sum(len(st.blocks) for st in eng.windows.values())
        # min-of-3 timed repetitions: each single loop is tens of ms, so
        # one-shot walls are dominated by host noise, not tracing cost
        wall = float("inf")
        for rep in range(3):
            t0 = time.time()
            for r in range(rounds):
                span = eng.tracer.root("bench_round")
                eng.batch_exec.execute(
                    late_items(), now=horizon + 2.0 + rep * rounds + r,
                    trace_parent=span)
                span.end()
            wall = min(wall, time.time() - t0)
        snap = eng.observability()
        out = {
            "wall_s": round(wall, 6),
            "fold_rows_per_sec": round(rows * rounds / max(wall, 1e-9)),
            "spans_finished": snap["trace"]["spans_finished"],
        }
        eng.close()
        return out

    rate0 = drive(0.0)
    rate1 = drive(1.0)
    overhead = (rate1["wall_s"] - rate0["wall_s"]) \
        / max(rate0["wall_s"], 1e-9) * 100.0
    out: Dict = {
        "num_windows": num_windows, "rounds": rounds,
        "events_per_window": events_per_window, "workload": op_name,
        "rate0": rate0, "rate1": rate1,
        "overhead_pct": round(overhead, 2),
        "pass_lt_5pct": overhead < 5.0,
    }
    if emit_json:
        merged = {}
        if os.path.exists(emit_json):
            with open(emit_json) as f:
                merged = json.load(f)
        merged["tracing_overhead"] = out
        with open(emit_json, "w") as f:
            json.dump(merged, f, indent=2)
    return out


def devices_sweep(num_windows: int = 16, events_per_window: int = 2000,
                  repeats: int = 5, op_name: str = "lrb",
                  num_keys: int = 64) -> Dict:
    """Slot-sharded multi-device fold vs BOTH single-device paths on the
    same workload. Run via ``--devices N`` (the flag forces N simulated
    CPU devices before jax initializes). The acceptance bar: sharded fold
    throughput no worse than single-device. Defaults to the keyed ``lrb``
    workload — the segment-axis-heavy regime the sharding targets: the
    dense one-hot fold costs O(rows * num_slots * num_keys), and each
    device reduces a D-times smaller row block onto a D-times narrower
    slot range (a slot's one-hot columns live on exactly one device), so
    per-device work drops ~D^2 (8 devices, CPU container: ~10x vs the
    unsharded batched fold, and above the per-window path too)."""
    import jax
    out = fold_benchmark(
        num_windows=num_windows, events_per_window=events_per_window,
        repeats=repeats,
        modes=(("batched", True, False), ("sharded", True, True),
               ("per_window", False, False)),
        op_name=op_name, num_keys=num_keys)
    out["num_devices"] = len(jax.devices())
    out["workload"] = op_name
    sharded = out["sharded"]["fold_events_per_sec"]
    out["sharded_vs_single_device"] = round(
        sharded / max(out["batched"]["fold_events_per_sec"], 1e-9), 2)
    out["sharded_vs_per_window"] = round(
        sharded / max(out["per_window"]["fold_events_per_sec"], 1e-9), 2)
    return out


def run(workload_names=("average", "bigrams", "stock_market", "lrb")
        ) -> List[Dict]:
    from repro.configs.workloads import WORKLOADS
    rows = []
    for name in workload_names:
        for include_late in (False, True):
            for baseline in (False, True):
                rows.append(run_one(WORKLOADS[name], baseline, include_late))
    return rows


if __name__ == "__main__":
    import argparse
    import os

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--devices", type=int, default=0, metavar="N",
                    help="simulate N CPU devices and benchmark the "
                         "slot-sharded fold against single-device "
                         "(sets XLA_FLAGS before jax loads)")
    ap.add_argument("--windows", type=int, default=0,
                    help="concurrent due windows (0 = each mode's "
                         "default: 16 for the devices sweep, 8 for "
                         "--gather — the configuration the checked-in "
                         "BENCH_q2_gather.json was measured at)")
    ap.add_argument("--gather", action="store_true",
                    help="run the pooled vs device-concat gather "
                         "benchmark and emit BENCH_q2_gather.json")
    ap.add_argument("--pipeline", action="store_true",
                    help="benchmark the pipelined async engine vs the "
                         "synchronous loop over cold p-blocks and merge "
                         "a pipeline_vs_sync ratio into "
                         "BENCH_q2_gather.json")
    ap.add_argument("--skew", action="store_true",
                    help="benchmark the split-K chunked fold vs the "
                         "stripe fold on a Zipf-skewed growing-late-"
                         "table workload and merge a splitk_vs_stripe "
                         "section into BENCH_q2_gather.json")
    ap.add_argument("--obs", action="store_true",
                    help="measure structured-tracing overhead (sample "
                         "rate 0.0 vs 1.0 on a fold-bound drive) and "
                         "merge a tracing_overhead section into "
                         "BENCH_q2_gather.json")
    args = ap.parse_args()
    if args.devices > 1 and (args.gather or args.pipeline or args.skew
                             or args.obs):
        ap.error("--gather/--pipeline/--skew/--obs measure single-"
                 "device paths; run them without --devices")
    if args.devices > 1:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.devices}").strip()
        print(devices_sweep(num_windows=args.windows or 16))
    elif args.gather:
        import json as _json
        print(_json.dumps(gather_benchmark(
            num_windows=args.windows or 8), indent=2))
    elif args.pipeline:
        import json as _json
        print(_json.dumps(pipeline_benchmark(
            num_windows=args.windows or 8), indent=2))
    elif args.skew:
        import json as _json
        print(_json.dumps(skew_benchmark(
            num_windows=args.windows or 8), indent=2))
    elif args.obs:
        import json as _json
        print(_json.dumps(obs_overhead_benchmark(
            num_windows=args.windows or 8), indent=2))
    else:
        for r in run():
            print(r)
        print(fold_benchmark())
