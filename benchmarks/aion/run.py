#!/usr/bin/env python3
"""Aion's on-chip benchmark: one run of one cell.

    python3 benchmarks/aion/run.py --workload <workload> \\
        --seed 7 --seconds 51 --trace 0

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a deployment
(``configs/<config>.json``) under a traffic mix
(``traffic/<traffic>.json``). The run:

1. set-up: builds the engine, streams the cell's history through the
   same calls the window makes (so its windows' state sits in the
   device arena, host memory and the log store), which also compiles or
   loads every program the window uses;
2. window: drives ``ingest``, ``advance_watermark``, ``poll`` once per
   step for ``--seconds`` of wall time, open or closed loop;
3. after the window: reads the device's memory peak, lets every planned
   re-execution fall due, frees the engine and compares every answer
   emitted in the window, and after it, with the plain float64
   reference over the events sent so far.

With ``--trace 0`` the result line holds the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from the window's host
timings and counters and from a profiler trace of a slice of it (see
``TRACE_FROM``). The last line of standard
output is one JSON object; the numbers compared, each beside its limit,
come last on standard error and in the result's ``checks``.

The run exits 2 with no result when JAX finds no TPU or fewer chips than
the cell asks for; it never falls back to the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()     # set-up is counted from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Optional  # noqa: E402

# the TPU runtime logs to a fixed directory under /tmp unless told not to
os.environ.setdefault("TPU_LOG_DIR", "disabled")

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
sys.path.insert(0, str(CHECKOUT / "src"))
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import trace_reduce  # noqa: E402
from gen import Generator  # noqa: E402

#: fixed paths inside the checkout: the compile cache's path is part of
#: its key; the store is removed at each run's start and end
CACHE_DIR = CHECKOUT / ".jax_cache"
STORE_DIR = CHECKOUT / ".bench_store"

#: the traced slice of a ``--trace 1`` window: it starts at this share of
#: the window, lasts at least ``TRACE_MIN_S``, then runs on until a
#: batched fold round has run inside it (they come every few seconds),
#: and ends at the window's end at the latest. A trace of a whole long
#: window takes minutes to collect and reduce.
TRACE_FROM = 0.3
TRACE_MIN_S = 15.0


def enable_compile_cache(path: Path) -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    # every program, however quick to compile, so that a warm run's
    # set-up compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def log(name: str, value) -> None:
    print(f"{name}: {value}", file=sys.stderr, flush=True)


def peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


class Tracer:
    """The profiler over a slice of the window (``TRACE_FROM``,
    ``TRACE_MIN_S``), with the engine's counters over the same steps.
    The profiler's data is collected on a thread of its own, so that the
    window's steps go on meanwhile."""

    def __init__(self, eng):
        self.eng = eng
        self.session = None
        self.t_start = 0.0
        self.c0 = self.c1 = None
        self.collector = None
        self.data = None

    def tick(self, drv, elapsed: float, seconds: float) -> None:
        """Before each step of the window, ``elapsed`` seconds in."""
        if self.session is None and self.c1 is None:
            if elapsed >= TRACE_FROM * seconds:
                self.session = trace_reduce.Session()
                self.c0 = harness.counters(self.eng)
                self.t_start = elapsed
                drv.annotate = True
        elif self.session is not None \
                and elapsed - self.t_start >= TRACE_MIN_S \
                and self.eng.metrics.batch_executions \
                > self.c0["batch_executions"]:
            self._stop(drv, background=True)

    def _stop(self, drv, background: bool) -> None:
        drv.annotate = False
        self.c1 = harness.counters(self.eng)
        session, self.session = self.session, None

        def collect():
            self.data = session.stop()
        if background:
            self.collector = threading.Thread(
                target=collect, name="bench-trace-collect")
            self.collector.start()
        else:
            collect()

    def finish(self, drv) -> Optional[dict]:
        """After the window: the trace's data and the slice's counters,
        or None when the window ended before the slice began."""
        if self.session is not None:
            self._stop(drv, background=False)
        if self.collector is not None:
            self.collector.join()
        if self.data is None:
            return None
        return {"data": self.data,
                "counters": harness.delta(self.c0, self.c1)}


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool, *,
        devices, t_start: float, store_dir: Path = STORE_DIR,
        make_engine=harness.make_engine, on_record=None) -> dict:
    """One run of ``cell``; returns the result line's object.
    ``on_record(steps, rec, watermark)``, where given, sees what the run
    recorded before the check (``readings.py`` takes the control's
    readings from it)."""
    cfg, traffic, ref = cell.config, cell.traffic, cell.reference
    compiles = harness.CompileLog()
    shutil.rmtree(store_dir, ignore_errors=True)
    eng = make_engine(cfg, store_dir, annotate=trace)
    arena = eng.pool.arena_bytes if eng.pool is not None else 0
    if cfg.get("arena_bytes") and arena != cfg["arena_bytes"]:
        eng.close()
        raise RuntimeError(f"arena of {arena} B, the configuration states "
                           f"{cfg['arena_bytes']} B")
    windows = harness.Windows.of(cfg)
    step_s = float(traffic["step_s"])
    per_step = int(round(cfg["event_rate"] * step_s))
    setup_events = (cfg["history_events"] if traffic["history"]
                    else int(traffic["warmup_windows"] * windows.size
                             * cfg["event_rate"]))
    producer = harness.Producer(Generator(cfg, traffic, seed, cell.events),
                                per_step, step_s)
    drv = harness.Driver(eng, windows, ref.COLUMNS)
    try:
        # ---- set-up: the history, through the window's own calls
        for _ in range(setup_events // per_step):
            now, batch = producer.next()
            drv.step(now, batch)
        n_setup = len(drv.steps)
        setup_peak = peak_bytes(devices)
        c0 = harness.counters(eng)
        compiles.warm = True
        setup_s = time.perf_counter() - t_start

        # ---- the measured window
        open_loop = traffic["loop"] == "open"
        step_wall = per_step / float(traffic["rate"]) if open_loop else 0
        tracer = Tracer(eng) if trace else None
        t0 = time.perf_counter()
        k = 0
        while True:
            t = time.perf_counter()
            due = t0 + k * step_wall if open_loop else t
            # a step due later, or not sent by the window's end (the
            # backlog of an open loop above capacity), is not sent
            if max(due, t) >= t0 + seconds:
                break
            if due > t:
                time.sleep(due - t)
            if tracer is not None:
                tracer.tick(drv, t - t0, seconds)
            now, batch = producer.next()
            drv.step(now, batch, window=True, due=due)
            k += 1
        t_end = time.perf_counter()
        traced = tracer.finish(drv) if tracer is not None else None
        peak = peak_bytes(devices)
        c1 = harness.counters(eng)
        compiles.warm = False
        producer.close()

        # ---- after the window: every planned re-execution falls due
        plans = [p.times[-1] for p in eng.reexec_plans.values() if p.times]
        drv.flush(max([drv.steps[-1].now] + plans) + step_s)
        watermark = drv.wm
    finally:
        producer.close()
        try:
            eng.close()
        finally:
            drv.eng = None
            del eng
            gc.collect()
            shutil.rmtree(store_dir, ignore_errors=True)

    steps = drv.steps
    win = steps[n_setup:-1]
    stale = harness.staleness(steps, t_end)
    rec = {
        "cell": cell.name, "config": cfg, "traffic": traffic,
        "setup_s": setup_s,
        "peak_bytes": peak,
        "device_kind": devices[0].device_kind,
        "window": {"seconds": t_end - t0, "steps": len(win),
                   "events": sum(len(s.ts) for s in win)},
        "host": {"ingest_s": sum(s.ingest_s for s in win),
                 "control_s": sum(s.control_s for s in win),
                 "round_s": sum(s.round_s for s in win)},
        "counters": harness.delta(c0, c1),
        "compiles": {"lowered": compiles.lowered,
                     "compiled": compiles.compiled},
        "gen_lag_s": ([s.sent - s.due for s in win] if open_loop else None),
        "staleness": stale,
        "trace": None,
    }
    log("set-up", f"{setup_s:.3f} s, {n_setup} steps, "
        f"{sum(len(s.ts) for s in steps[:n_setup])} events")
    log("set-up counters", c0)
    log("device memory peak after set-up", setup_peak)
    log("window", f"{rec['window']['seconds']:.3f} s, {len(win)} steps, "
        f"{rec['window']['events']} events")
    log("window counters", rec["counters"])
    log("window compiles", f"{compiles.lowered} lowered "
        f"({compiles.compiled} compiled) {dict(compiles.names)}")
    log("late events unanswered at the window's end", stale["unanswered"])
    if open_loop:
        lag = rec["gen_lag_s"]
        log("generator lag s (p50, p95, max, last)",
            [float(np.percentile(lag, 50)), float(np.percentile(lag, 95)),
             max(lag), lag[-1]] if lag else None)
    if traced is not None:
        rec["trace"] = trace_reduce.reduce(traced["data"],
                                           harness.fold_kernels(cfg))
        if rec["trace"] is not None:
            rec["trace"]["counters"] = traced["counters"]
        del traced
        log("trace", {k: v for k, v in (rec["trace"] or {}).items()
                      if k not in ("device_ops", "idle_gaps")})

    if on_record is not None:
        on_record(steps, rec, watermark)

    # ---- the check, once the engine's state is freed
    verdict = harness.check(steps, ref, windows, cfg["num_keys"],
                            watermark)
    log("answers compared", f"{verdict['attempted']} "
        f"({verdict['missing']} due and never given)")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        read = harness.reader(m["name"])
        value = read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    out = {
        "correct": verdict["failed"] == 0 and verdict["attempted"] > 0,
        "attempted": verdict["attempted"],
        "failed": verdict["failed"],
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": peak},
    }
    if trace and rec["trace"] is not None:
        out["device"]["busy_s"] = rec["trace"]["busy_s"]
        out["device"]["window_s"] = rec["trace"]["window_s"]
        out["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                            "idle_gaps": rec["trace"]["idle_gaps"]}
    checks = {k: {"value": v, "limit": ref.LIMITS[k]}
              for k, v in verdict["numbers"].items()}
    checks["answers_never_given"] = {"value": verdict["missing"],
                                     "limit": 0}
    out["checks"] = checks
    for k, c in checks.items():
        log(f"check {k}", f"{c['value']!r} (limit {c['limit']!r})")
    return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def chips(workload: str):
    """The TPU chips the cell asks for, or None (said on standard error)
    when JAX finds no TPU or too few."""
    import jax
    devices = jax.devices()
    if not devices or devices[0].platform != "tpu":
        plat = devices[0].platform if devices else "none"
        print(f"run.py: no TPU found (JAX platform: {plat})",
              file=sys.stderr)
        return None
    want = next(w["chips"] for w in
                json.loads((CHECKOUT / "BENCHMARK.json").read_text())
                ["workloads"] if w["name"] == workload)
    if len(devices) < want:
        print(f"run.py: {want} chips asked for, {len(devices)} found",
              file=sys.stderr)
        return None
    return devices[:want]


def main(argv=None) -> int:
    args = parse(argv)
    cell = harness.load_cell(args.workload)
    devices = chips(args.workload)
    if devices is None:
        return 2
    enable_compile_cache(CACHE_DIR)
    log("device", f"{devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}")
    out = run(cell, args.seed, args.seconds, bool(args.trace),
              devices=devices, t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
