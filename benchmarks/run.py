"""Benchmark entry point: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (scaffold contract): for
engine benchmarks us_per_call is microseconds per ingested event; derived
carries the headline metric of that table.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path


def _csv(name, us_per_call, derived):
    print(f"{name},{us_per_call:.3f},{derived}")


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    t_all = time.time()
    from benchmarks import q1_memory, q2_throughput, q3_ablation, q4_staleness

    # ---- Q1: memory pressure (Fig. 2)
    for r in q1_memory.run():
        name = f"q1_{r['workload']}_{r['backend']}_pw{r['past_windows']}"
        derived = (f"median_device_mb={r['median_device_mb']:.1f};"
                   f"oom_at={r['oom_at_watermark']}")
        _csv(name, 1e6 * r["seconds"] / 15000, derived)

    # ---- Q2: throughput overhead (Figs. 3-5)
    for r in q2_throughput.run():
        tag = "late" if r["late_included"] else "normal"
        name = f"q2_{r['workload']}_{r['backend']}_{tag}"
        _csv(name, 1e6 / max(r["events_per_sec"], 1e-9),
             f"events_per_sec={r['events_per_sec']:.0f};"
             f"stall_s={r['fetch_stall_s']}")

    # ---- Q3: per-optimization ablations (Fig. 8)
    for r in q3_ablation.run():
        name = f"q3_{r['variant']}"
        _csv(name, 1e6 / max(r["events_per_sec"], 1e-9),
             f"sim_io_s={r['sim_io_s']};stall_s={r['fetch_stall_s']};"
             f"peak_mb={r['peak_device_mb']:.1f};"
             f"preempt={r['preemptions']}")

    # ---- Q4: staleness trigger (Fig. 9)
    q4 = q4_staleness.run()
    for r in q4["staleness_vs_executions"]:
        _csv(f"q4_maxstaleness_k{r['k']}", 0.0,
             f"aion={r['aion']:.4f};deltat={r['deltat']:.4f};"
             f"deltaev={r['deltaev']:.4f}")
    for r in q4["executions_for_bounds"]:
        _csv(f"q4_execs_{r['dist']}_b{r['bound']}", 0.0,
             f"aion={r['aion']};deltat={r['deltat']};"
             f"deltaev={r['deltaev']}")

    # ---- Roofline (from dry-run records, if present)
    dryrun = Path("experiments/dryrun")
    if dryrun.exists() and any(dryrun.glob("*.json")):
        from benchmarks import roofline
        rows = roofline.main(quiet=True)
        for r in rows:
            name = f"roofline_{r['mesh']}_{r['arch']}_{r['shape']}"
            bound_s = max(r["compute_s"], r["memory_s"], r["collective_s"])
            _csv(name, bound_s * 1e6,
                 f"dominant={r['dominant']};frac={r['roofline_fraction']:.3f};"
                 f"fits={r['fits_hbm']}")

    print(f"# total benchmark wall time: {time.time()-t_all:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
