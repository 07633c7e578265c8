#!/usr/bin/env python3
"""On-chip smoke run of the stream engine at a deployment's real size.

The paper's Table-1 stock-market workload streams through ``StreamEngine``
on a TPU: 128 symbols, 30 s tumbling windows, 1664-byte events (416
float32 lanes), 10,000 events/s with the generator's log-normal lateness,
for six windows (300k events, ~0.5 GB each) and then late waves that
re-execute the closed windows. The engine runs its default batched path:
a ~2 GiB device block pool inside a 4.25 GiB device budget, the Pallas
block-table fold with split-K chunks, a 512 MiB host tier, and the
log-structured store on local disk for the rest. Every result the engine
emits is checked against the float64 numpy oracle
(``repro.testing.oracles``) over the events ingested so far.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # slot-sharded fold on four chips,
                                       # compared with the one-chip fold

The run exits non-zero, without a result line, when JAX finds no TPU,
when any fold resolved to the dense or interpreter backend, when the
device memory peak passes ``Plan.peak_hbm_bound``, and on any failed
phase or oracle mismatch. Otherwise the last line of standard
output is ``{"ok": true, "device": {...}}``. Everything runs in this one
process; the store directory inside the checkout is removed at the end.
"""
from __future__ import annotations

import argparse
import collections
import json
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

#: mean = sum / count in float32 against a float64 oracle; a bf16 pass
#: over the prices (~4e-3 relative) would fail it
MEAN_RTOL = 1e-4
#: min and max are exact float32 inputs: any difference is a wrong event
EXTREMA_RTOL = 0.0


@dataclass
class Plan:
    """The deployment's scale and the run's cuts. Shapes (symbols, window,
    payload width, lateness model) are the workload's own and have no
    knob here."""
    windows: int = 6               # stream length, in windows
    rate: int = 10_000             # events/s (Table 1)
    step: float = 1.0              # processing seconds per ingest batch
    late_waves: int = 3            # late-only batches after the stream
    late_wave_events: int = 30_000
    block_size: int = 512
    pool_slots: int = 2560         # 2560 x 512 x 1668 B = 2.04 GiB arena
    device_budget: int = 4352 << 20            # 4.25 GiB; arena <= half
    host_budget: int = 512 << 20
    splitk_chunk_rows: int = 128
    warmup_windows: int = 2        # compiles after this are counted
    time_budget: float = 720.0     # seconds for every phase; cuts windows
    # the budgets bound retained state; a fold round adds a transient on
    # top of the device budget: a functional copy of the arena while the
    # round pins it, the round's stacked fill flush and its stacked
    # fallback groups. 10 GiB = the 4.25 GiB budget + 5.75 GiB of that.
    peak_hbm_bound: int = 10 << 30


def _no_tpu(devices) -> str:
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        return f"no TPU found (JAX platform: {plat})"
    return ""


class CompileLog:
    """Backend compilations (and persistent-cache loads) by function
    name, split at the end of warm-up."""

    def __init__(self):
        import jax
        self.warm = False
        self.before = 0
        self.after = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event != "/jax/core/compile/backend_compile_duration":
            return
        if self.warm:
            self.after[kw.get("fun_name", "?")] += 1
        else:
            self.before += 1


class Oracle:
    """Events ingested so far, per window, and the emission checks."""

    def __init__(self, window: float, num_keys: int):
        self.window = window
        self.num_keys = num_keys
        self.events = collections.defaultdict(list)   # wid -> arrays
        self.checked = 0
        self.worst_mean = 0.0
        self.worst_extrema = 0.0

    def add(self, batch) -> None:
        from repro.core.windows import WindowId
        import numpy as np
        # the assignment TumblingWindows makes
        wstart = np.floor(batch.timestamps / self.window) * self.window
        for s in set(wstart.tolist()):
            sel = wstart == s
            self.events[WindowId(float(s), float(s) + self.window)].append(
                (batch.keys[sel], batch.timestamps[sel],
                 batch.values[sel, :1].copy()))

    def forget(self, wid) -> None:
        """The engine purged ``wid``: later events start a new window."""
        self.events.pop(wid, None)

    def want(self, wid) -> dict:
        from repro.testing.oracles import oracle_stock
        import numpy as np
        parts = self.events[wid]
        if len(parts) > 1:
            parts[:] = [tuple(np.concatenate(c) for c in zip(*parts))]
        keys, ts, vals = parts[0]
        return oracle_stock(keys, ts, vals, self.window,
                            self.num_keys)[wid]

    def check(self, results: dict, seen: dict, emitted: list) -> None:
        """Check every result object of one engine not ``seen`` before
        (an execution always makes a new one) and note its window in
        ``emitted``."""
        for wid, got in results.items():
            if seen.get(wid) is got:
                continue
            seen[wid] = got
            emitted.append(wid)
            self.compare(wid, got, self.want(wid))
            self.checked += 1

    def compare(self, wid, got: dict, want: dict) -> None:
        import numpy as np
        present = np.isfinite(want["min"])
        for k in ("min", "max"):
            g = np.asarray(got[k], np.float64)
            if not np.array_equal(np.isfinite(g), present):
                raise AssertionError(f"{wid}: {k} has the wrong symbols")
            err = np.max(np.abs(g - want[k])[present]
                         / np.abs(want[k][present]), initial=0.0)
            self.worst_extrema = max(self.worst_extrema, float(err))
            if err > EXTREMA_RTOL:
                raise AssertionError(f"{wid}: {k} off by {err:.3e}")
        g = np.asarray(got["mean"], np.float64)
        err = np.max(np.abs(g - want["mean"])[present]
                     / np.maximum(np.abs(want["mean"][present]), 1e-30),
                     initial=0.0)
        self.worst_mean = max(self.worst_mean, float(err))
        if err > MEAN_RTOL:
            raise AssertionError(f"{wid}: mean off by {err:.3e}")
        if not np.array_equal(np.asarray(got["alerts"])[present],
                              want["alerts"][present]):
            raise AssertionError(f"{wid}: alerts differ")


def make_engine(plan: Plan, store_dir: Path, shard_devices: int = 0):
    from repro.configs.base import AionConfig
    from repro.configs.workloads import STOCK_MARKET
    from repro.core import StreamEngine, TumblingWindows
    from repro.core.operators import make_operator
    wl = STOCK_MARKET
    width = wl.resolved_value_width()
    aion = AionConfig(block_size=plan.block_size,
                      pool_slots=plan.pool_slots,
                      splitk_chunk_rows=plan.splitk_chunk_rows,
                      slot_sharding=shard_devices > 1,
                      slot_shard_devices=shard_devices)
    return StreamEngine(
        assigner=TumblingWindows(wl.window_duration),
        operator=make_operator("stock", aion.block_size, width,
                               num_keys=wl.num_keys),
        aion=aion, value_width=width,
        device_budget_bytes=plan.device_budget,
        host_budget_bytes=plan.host_budget,
        spill_dir=store_dir)


def drive(plan: Plan, seed: int, engines: list, oracle: Oracle,
          compiles: CompileLog, t0: float, on_emit=None) -> dict:
    """Stream the workload through every engine in lock step, checking
    each engine's emissions after every call. Returns what ran."""
    from repro.configs.workloads import STOCK_MARKET
    from repro.data.generators import make_generator
    wd = STOCK_MARKET.window_duration
    gen = make_generator(STOCK_MARKET, seed=seed)
    per_step = int(plan.rate * plan.step)
    gen_s = 0.0
    window_s = []
    windows_run = 0
    emitted = [[] for _ in engines]
    seen = [{} for _ in engines]

    def each(call):
        for i, eng in enumerate(engines):
            call(eng)
            before = len(emitted[i])
            oracle.check(eng.results, seen[i], emitted[i])
            if on_emit is not None and len(emitted[i]) > before:
                on_emit(i, emitted[i][before:])

    def poll(now):
        def one(eng):
            live = set(eng.windows)
            eng.poll(now)
            for wid in live - set(eng.windows):
                oracle.forget(wid)
        each(one)

    def ingest(batch, now):
        oracle.add(batch)
        each(lambda eng: eng.ingest(batch, now))

    now = 0.0
    while windows_run < plan.windows:
        w0 = time.time()
        if window_s:
            per_window = max(window_s)
            left = t0 + plan.time_budget - w0
            # keep room for this window and the late waves after it
            if per_window * (2 + plan.late_waves / 2) > left:
                break
        end = (windows_run + 1) * wd
        while now < end:
            g0 = time.time()
            batch = gen.batch(per_step, now)
            gen_s += time.time() - g0
            ingest(batch, now)
            each(lambda eng: eng.advance_watermark(now, now))
            poll(now)
            now += plan.step
        windows_run += 1
        window_s.append(time.time() - w0)
        m = engines[-1].metrics
        report(f"window {windows_run}",
               f"{window_s[-1]:.1f} s, {m.ingested} events, "
               f"{m.late_executions} late executions, "
               f"{m.batch_executions} rounds, {m.pooled_rows} pooled / "
               f"{m.fallback_rows} fallback rows, {hbm()}")
        if windows_run == plan.warmup_windows:
            compiles.warm = True

    # close every window, then late-only waves into the closed ones
    wm = now
    each(lambda eng: eng.advance_watermark(wm, now))
    compiles.warm = True
    import numpy as np
    for _ in range(plan.late_waves):
        g0 = time.time()
        batch = gen.batch(plan.late_wave_events, wm)
        gen_s += time.time() - g0
        ingest(batch, now)
        span = 2 * engines[0].cleanup.current_bound()
        for t in np.linspace(now, now + span, 10):
            poll(float(t))
        now = float(t)
        m = engines[-1].metrics
        report("late wave", f"{m.late_executions} late executions, "
               f"{m.batch_executions} rounds, {m.demand_pool_fills} demand "
               f"fills, {m.fallback_rows} fallback rows, {hbm()}")
    for eng in engines:
        eng.io.drain()
    return {"windows": windows_run, "gen_s": gen_s, "window_s": window_s,
            "emitted": [len(e) for e in emitted]}


def fold_backends() -> dict:
    from repro.kernels import ops
    return {f"{entry}/{be}": n
            for (entry, be), n in sorted(ops.resolved_backends.items())}


def report_backends(backends: dict, out: dict) -> None:
    report("fold backends",
           f"{backends} (batched rounds); {out['per-window executions']} "
           "per-window executions ran the XLA scatter fold")


def check_backends(backends: dict) -> None:
    if not backends:
        raise AssertionError("no segment fold ran")
    slow = [k for k in backends if not k.endswith("/pallas")]
    if slow:
        raise AssertionError(f"folds left the Pallas path: {slow}")


def report(name: str, value) -> None:
    print(f"{name}: {value}", flush=True)


def host_peak() -> int:
    """The process's resident-set high-water mark, in bytes."""
    import resource
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def check_peak(plan: Plan, peaks: list) -> None:
    if any(p is None or p > plan.peak_hbm_bound for p in peaks):
        raise AssertionError(f"peak_bytes_in_use {peaks} over the "
                             f"{plan.peak_hbm_bound} B bound")


def hbm() -> str:
    """Device memory now and at its high-water mark, on the first chip."""
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return (f"{stats.get('bytes_in_use')} B in use, "
            f"peak {stats.get('peak_bytes_in_use')} B")


def engine_report(eng, tag: str = "") -> dict:
    m = eng.metrics
    io = eng.io.stats
    out = {
        "events ingested": m.ingested,
        "late events": m.ingested_late,
        "live executions": m.live_executions,
        "late executions": m.late_executions,
        "batched rounds": m.batch_executions,
        "batched window executions": m.batched_windows,
        "per-window executions": (m.live_executions + m.late_executions
                                  - m.batched_windows),
        "pooled rows": m.pooled_rows,
        "fallback rows": m.fallback_rows,
        "split-K launches": m.splitk_launches,
        "demand pool fills": m.demand_pool_fills,
        "spilled blocks": io["spilled_blocks"],
        "staged blocks": io["staged_blocks"],
        "arena bytes": eng.pool.arena_bytes if eng.pool else 0,
        "pool slots": eng.pool.pool_slots if eng.pool else 0,
    }
    for k, v in out.items():
        report(f"{tag}{k}", v)
    return out


def require(out: dict, windows: int) -> None:
    if out["late executions"] <= 0:
        raise AssertionError("no late execution ran")
    if out["spilled blocks"] <= 0:
        raise AssertionError("nothing spilled to the store")
    if out["arena bytes"] < (2 << 30):
        raise AssertionError(f"arena of {out['arena bytes']} B < 2 GiB")
    if out["live executions"] < windows:
        raise AssertionError("a closed window was never executed live")


def run_one_chip(plan: Plan, seed: int, store: Path) -> None:
    import jax
    t0 = time.time()
    compiles = CompileLog()
    from repro.configs.workloads import STOCK_MARKET
    oracle = Oracle(STOCK_MARKET.window_duration, STOCK_MARKET.num_keys)
    eng = make_engine(plan, store / "one")
    try:
        ran = drive(plan, seed, [eng], oracle, compiles, t0)
        report("windows", f"{ran['windows']} of {plan.windows} "
               f"({STOCK_MARKET.window_duration:.0f} s, "
               f"{plan.rate} events/s)")
        if ran["windows"] < plan.windows:
            report("cut", f"windows {plan.windows} -> {ran['windows']} "
                   f"to stay inside {plan.time_budget:.0f} s")
        out = engine_report(eng)
    finally:
        eng.close()
    backends = fold_backends()
    report_backends(backends, out)
    check_backends(backends)
    report("emissions checked", oracle.checked)
    report("oracle worst relative error",
           f"mean {oracle.worst_mean:.3e} (limit {MEAN_RTOL:g}), "
           f"min/max {oracle.worst_extrema:.3e} "
           f"(limit {EXTREMA_RTOL:g})")
    report("compilations during warm-up", compiles.before)
    report("compilations after warm-up",
           f"{sum(compiles.after.values())} {dict(compiles.after)}")
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    report("peak_bytes_in_use", f"{peak} (bound {plan.peak_hbm_bound})")
    report("host max RSS bytes", host_peak())
    report("wall seconds",
           f"{time.time() - t0:.1f} (data generation {ran['gen_s']:.1f}, "
           f"per window {[round(s, 1) for s in ran['window_s']]})")
    require(out, ran["windows"])
    check_peak(plan, [peak])


def run_four_chips(plan: Plan, seed: int, store: Path) -> None:
    """The slot-sharded fold over a four-chip mesh against the one-chip
    fold, on the same stream in the same process: every result the
    sharded engine emits must equal the one-chip engine's result for
    that window, and both must match the oracle."""
    import jax
    import numpy as np
    t0 = time.time()
    compiles = CompileLog()
    from repro.configs.workloads import STOCK_MARKET
    oracle = Oracle(STOCK_MARKET.window_duration, STOCK_MARKET.num_keys)
    four = make_engine(plan, store / "four", shard_devices=4)
    # before the one-chip engine exists: each chip holds its quarter
    report("bytes_in_use per chip with the sharded arena alone",
           [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()[:4]])
    one = make_engine(plan, store / "one")
    pairs = {"compared": 0}

    def on_emit(i, wids):
        if i != 1:
            return
        for wid in wids:
            a, b = one.results[wid], four.results[wid]
            for k in ("min", "max", "alerts"):
                if not np.array_equal(np.asarray(a[k]), np.asarray(b[k])):
                    raise AssertionError(f"{wid}: sharded {k} differs")
            np.testing.assert_allclose(b["mean"], a["mean"],
                                       rtol=MEAN_RTOL, err_msg=str(wid))
            pairs["compared"] += 1

    try:
        shards = [s.data.nbytes
                  for s in four.pool.values.addressable_shards]
        ran = drive(plan, seed, [one, four], oracle, compiles, t0,
                    on_emit=on_emit)
        report("windows", f"{ran['windows']} of {plan.windows}")
        if ran["windows"] < plan.windows:
            report("cut", f"windows {plan.windows} -> {ran['windows']} "
                   f"to stay inside {plan.time_budget:.0f} s")
        engine_report(one, "one chip: ")
        out = engine_report(four, "four chips: ")
        report("four chips: sharded fold rounds",
               four.metrics.sharded_batch_executions)
    finally:
        one.close()
        four.close()
    backends = fold_backends()
    report_backends(backends, out)
    check_backends(backends)
    report("sharded vs one-chip results compared", pairs["compared"])
    report("emissions checked against the oracle", oracle.checked)
    report("oracle worst relative error",
           f"mean {oracle.worst_mean:.3e}, min/max "
           f"{oracle.worst_extrema:.3e}")
    report("values arena bytes per chip", shards)
    report("bytes_in_use per chip",
           [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()[:4]])
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:4]]
    report("peak_bytes_in_use per chip",
           f"{peaks} (bound {plan.peak_hbm_bound} each)")
    report("host max RSS bytes", host_peak())
    report("compilations after warm-up", sum(compiles.after.values()))
    report("wall seconds", f"{time.time() - t0:.1f}")
    if four.metrics.sharded_batch_executions <= 0:
        raise AssertionError("no fold round ran sharded")
    if pairs["compared"] <= 0:
        raise AssertionError("no sharded result to compare")
    if len(set(shards)) != 1 or len(shards) != 4:
        raise AssertionError(f"arena not split in quarters: {shards}")
    require(out, ran["windows"])
    check_peak(plan, peaks)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    why = _no_tpu(devices)
    if why:
        print(f"chip_smoke: {why}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked for, "
              f"{len(devices)} found", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    report("compile cache", enable_compile_cache())
    d = devices[0]
    report("device", f"{d.platform} {d.device_kind} x{len(devices)}")

    plan = Plan()
    if args.chips == 4:
        # two engines share one process and its host cores, on four
        # chips' worth of chip time: half the stream, fewer late waves
        plan.windows, plan.late_waves = 3, 2
        report("cut", f"windows {Plan.windows} -> {plan.windows}, late "
               f"waves {Plan.late_waves} -> {plan.late_waves} for the "
               "four-chip comparison")
    store = ROOT / ".smoke_store"
    shutil.rmtree(store, ignore_errors=True)
    try:
        if args.chips == 4:
            run_four_chips(plan, args.seed, store)
        else:
            run_one_chip(plan, args.seed, store)
    finally:
        shutil.rmtree(store, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
