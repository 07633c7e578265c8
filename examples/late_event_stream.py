"""The paper's three mechanisms, end to end, on one stream.

1. predictive cleanup — the engine learns the lateness distribution and
   tightens the purge bound from the conservative default;
2. staleness trigger — minimum re-executions to meet the staleness SLA,
   compared against the deltat/deltaev baselines (Fig. 9);
3. proactive caching — fetch-stall with and without pre-staging.

    PYTHONPATH=src python examples/late_event_stream.py
"""
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.cleanup import PredictiveCleanup
from repro.core.staleness import (
    deltaev_times, deltat_times, executions_for_bound, max_staleness_of,
    minimize_max_staleness,
)
from repro.data.generators import lateness_delays

T = 100.0
rng = np.random.default_rng(0)


def cleanup_demo():
    print("== predictive cleanup: adaptive max-allowed-lateness bound")
    c = PredictiveCleanup(coverage=0.99, confidence=0.95,
                          initial_bound=3600.0, min_history=100)
    for n in (100, 1000, 20000):
        c.observe(lateness_delays("lnorm", n, T, rng))
        print(f"  after {c.hist.total:6d} observations: "
              f"bound = {c.current_bound():9.2f}s "
              f"(conservative start was 3600s)")


def trigger_demo():
    print("\n== staleness trigger vs deltat/deltaev (paper Fig. 9)")
    delays = lateness_delays("lnorm", 20000, T, rng)
    print(f"  {'K':>3s} {'aion':>9s} {'deltat':>9s} {'deltaev':>9s}")
    for k in (4, 8, 16):
        a = minimize_max_staleness(delays, T, k).max_staleness
        d = max_staleness_of(deltat_times(T, k), delays, T)
        e = max_staleness_of(deltaev_times(delays, T, k), delays, T)
        print(f"  {k:3d} {a:9.4f} {d:9.4f} {e:9.4f}")
    for bound in (0.1, 0.05, 0.01):
        ka = executions_for_bound(
            lambda k: minimize_max_staleness(delays, T, k).times,
            delays, T, bound)
        kt = executions_for_bound(lambda k: deltat_times(T, k), delays, T,
                                  bound)
        ke = executions_for_bound(lambda k: deltaev_times(delays, T, k),
                                  delays, T, bound)
        print(f"  bound {bound}: aion needs K={ka}, deltat K={kt}, "
              f"deltaev K={ke}")


def prestage_demo():
    print("\n== proactive caching: fetch stall with/without pre-staging")
    from benchmarks.q3_ablation import run_one
    for variant in ("aion-full", "no-pre-stgng"):
        r = run_one(variant)
        print(f"  {variant:14s} fetch_stall={r['fetch_stall_s']:.3f}s "
              f"late_execs={r['late_execs']}")


if __name__ == "__main__":
    enable_compile_cache()
    cleanup_demo()
    trigger_demo()
    prestage_demo()
