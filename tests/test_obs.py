"""Unified observability layer (ISSUE 10).

Tentpole: the shared metrics registry behind every legacy ``.stats`` /
``EngineMetrics`` surface, structured tracing with EXPLICIT parent
handoff across threads, and the one-call ``engine.observability()``
snapshot + Prometheus/JSON exporters.

Satellites pinned here:
  1. ``TransferExecutor.stats`` under concurrent hammering — counts are
     exact (the old dict read-modify-write lost increments).
  2. Every unbounded metrics list is capped (``StoreHealth.transitions``
     via ``AionConfig.health_transitions_max``).
  3. Cross-thread trace propagation: the pipelined fold-round span
     parents back to the watermark-advance span, and a retried I/O task
     span records each backoff attempt — asserted on the JSON-lines
     export, not internal state.
"""
import json
import threading

import numpy as np
import pytest

from repro.configs.base import AionConfig
from repro.core import (
    EventBatch, InMemoryPolicy, StreamEngine, TumblingWindows,
    make_operator,
)
from repro.core.health import StoreHealth
from repro.core.pipeline import MultiTenantEngine, TenantSpec
from repro.core.staging import TransferExecutor
from repro.obs import (
    BoundedSeries, MetricsRegistry, NULL_SPAN, StatsMap, Tracer,
    to_json, to_prometheus,
)
from repro.testing.faults import FaultInjector, FaultyBlockStore


def _batch(n, width=1, seed=0, lo=0.0, hi=10.0, keys=8):
    rng = np.random.default_rng(seed)
    return EventBatch(rng.integers(0, keys, n), rng.uniform(lo, hi, n),
                      rng.normal(size=(n, width)).astype(np.float32))


def _engine(tmp_path, store=None, **aion_kw):
    aion = AionConfig(block_size=32, **aion_kw)
    return StreamEngine(
        assigner=TumblingWindows(10.0),
        operator=make_operator("average", aion.block_size, 1),
        aion=aion, value_width=1,
        spill_dir=None if store is not None else tmp_path, store=store)


# ============================================================= registry
def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("obs_test_ops", "ops")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)                          # counters only go up
    g = reg.gauge("obs_test_level")
    g.set(3)
    g.set(1)
    assert g.value == 1
    h = reg.histogram("obs_test_lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    snap = h.default.snapshot()
    assert snap["count"] == 3
    assert snap["sum"] == pytest.approx(5.55)


def test_labels_are_distinct_children():
    reg = MetricsRegistry()
    fam = reg.counter("obs_test_tasks", labelnames=("tenant",))
    fam.labels("a").inc(2)
    fam.labels("b").inc(5)
    assert fam.labels("a").value == 2
    assert fam.labels("b").value == 5
    # get-or-create: same labels -> same child
    assert fam.labels("a") is fam.labels("a")


def test_registry_rejects_kind_and_label_conflicts():
    reg = MetricsRegistry()
    reg.counter("obs_test_x")
    with pytest.raises(TypeError):
        reg.gauge("obs_test_x")
    reg.counter("obs_test_y", labelnames=("tenant",))
    with pytest.raises(ValueError):
        reg.counter("obs_test_y", labelnames=("shard",))


def test_bounded_series_caps_and_stays_a_list():
    s = BoundedSeries(maxlen=8)
    for i in range(100):
        s.append(i)
    assert len(s) <= 8
    assert s[-1] == 99
    assert isinstance(s, list)
    unbounded = BoundedSeries(0)
    unbounded.extend(range(100))
    assert len(unbounded) == 100


def test_statsmap_behaves_like_the_legacy_dict():
    reg = MetricsRegistry()
    st = StatsMap(reg, "obs_test_io")
    st.register_many(["staged", "errors"])
    st.register_raw("last_error")
    st["staged"] += 3                      # legacy read-modify-write
    st.inc("staged")
    assert st["staged"] == 4
    st["last_error"] = "disk on fire"      # non-numeric -> raw slot
    assert "disk on fire" in st["last_error"]
    st.update({"new_counter": 7})          # unknown key auto-registers
    assert st["new_counter"] == 7
    assert st.get("missing", 42) == 42
    snap = st.copy()
    assert isinstance(snap, dict) and snap["staged"] == 4
    assert st == snap                      # Mapping equality both ways
    # and the registry sees the same numbers under the prefix
    assert reg.snapshot()["obs_test_io_staged"] == 4


# ===================================== satellite 1: executor stat races
def test_executor_stats_exact_under_concurrent_hammering():
    """16 threads x 50 tasks (half of them failing) through the pooled
    executor: ``executed``/``errors`` must be exact. The legacy plain
    dict ``stats["executed"] += 1`` lost increments under this load."""
    ex = TransferExecutor(sequential_io=False, max_pool_workers=8)
    threads, per_thread = 16, 50
    try:
        handles = []
        hlock = threading.Lock()

        def hammer(k):
            for i in range(per_thread):
                if (k + i) % 2:
                    h = ex.submit(0, lambda: None)
                else:
                    def boom():
                        raise IOError("injected")
                    h = ex.submit(0, boom)
                with hlock:
                    handles.append(h)
        ts = [threading.Thread(target=hammer, args=(k,))
              for k in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert ex.drain(timeout=60)
        total = threads * per_thread
        fails = sum(1 for k in range(threads)
                    for i in range(per_thread) if not (k + i) % 2)
        assert ex.stats["executed"] == total
        assert ex.stats["errors"] == fails
    finally:
        ex.shutdown()


# ==================================== satellite 2: bounded metrics lists
def test_health_transitions_bounded():
    h = StoreHealth(error_threshold=1, cooldown_ticks=1,
                    max_transitions=16)
    for _ in range(200):                   # flap hard
        h.tick(5)
        h.tick(0)
        h.tick(0)
    assert len(h.transitions) <= 16
    assert isinstance(h.transitions, BoundedSeries)


def test_engine_wires_health_transitions_cap(tmp_path):
    eng = _engine(tmp_path, breaker_error_threshold=2,
                  health_transitions_max=8)
    assert eng.health is not None
    assert eng.health.transitions.maxlen == 8
    # the metrics field aliases the breaker's log (single source of truth)
    assert eng.metrics.ladder_transitions is eng.health.transitions
    eng.close()


# =============================================================== tracing
def test_sample_rate_zero_records_nothing(tmp_path):
    eng = _engine(tmp_path)                # trace_sample_rate defaults 0
    eng.ingest(_batch(64), now=1.0)
    eng.advance_watermark(10.0, now=2.0)
    eng.poll(3.0)
    eng.close()
    assert eng.tracer.records() == []
    assert eng.tracer.stats()["spans_started"] == 0
    assert not eng.tracer.root("x").sampled     # NULL span on the path


def test_trace_ring_is_bounded():
    tr = Tracer(sample_rate=1.0, capacity=4)
    for i in range(10):
        tr.root(f"s{i}").end()
    st = tr.stats()
    assert st["ring_len"] == 4
    assert st["spans_dropped"] == 6


def test_fold_round_span_parents_watermark_advance_across_threads(
        tmp_path):
    """Satellite 3a: the fold runs on the pipeline worker thread; its
    span must still parent back to the submitting watermark-advance
    span via the EXPLICIT handoff (no thread-locals to lose it)."""
    eng = _engine(tmp_path, trace_sample_rate=1.0,
                  pipelined_execution=True)
    eng.ingest(_batch(600, hi=40.0), now=1.0)
    eng.advance_watermark(50.0, now=2.0)
    assert eng.pipeline.drain(timeout=30.0)
    eng.close()
    recs = {r["span"]: r for r in eng.tracer.records()}
    folds = [r for r in recs.values() if r["name"] == "fold_round"]
    assert folds, "no fold_round span recorded"
    for f in folds:
        parent = recs[f["parent"]]
        assert parent["name"] == "watermark_advance"
        assert f["thread"] != parent["thread"]      # crossed a thread
        assert f["trace"] == parent["trace"]
        assert f["attrs"]["windows"] >= 1
        assert any(e["name"] == "emit" for e in f["events"])


def test_retried_io_span_records_each_backoff_attempt(tmp_path):
    """Satellite 3b: a transiently failing store ``get`` retries with
    backoff; the demand-stage span must carry one ``retry`` event per
    attempt — asserted on the JSON-lines export."""
    from repro.storage import make_store
    inj = FaultInjector(seed=0)
    store = FaultyBlockStore(
        make_store("log", tmp_path / "store"), inj)
    eng = _engine(tmp_path, store=store, trace_sample_rate=1.0,
                  io_retry_limit=4, io_retry_backoff=0.001)
    eng.ingest(_batch(256), now=1.0)
    state = next(iter(eng.windows.values()))
    for blk in list(state.blocks):
        eng.io.destage_block_sync(blk)
    # push the host copies all the way to the persistent tier so the
    # demand stage must call store.get (where the injector lives)
    eng.io.spill_blocks_sync(list(state.blocks))
    inj.fail_next("get", 2)                # two failures, then success
    root = eng.tracer.root("test_demand")
    h = eng.io.request_stage(state, demand=True, parent=root)
    assert h.wait_checked(30.0)
    root.end()
    assert eng.io.drain(timeout=30)
    eng.close()
    lines = [json.loads(l)
             for l in eng.tracer.export_jsonl().splitlines()]
    stages = [r for r in lines if r["name"] == "io.demand_stage"]
    assert stages, "no demand-stage span exported"
    retries = [e for r in stages for e in r["events"]
               if e["name"] == "retry"]
    assert len(retries) == 2
    assert [e["attempt"] for e in retries] == [1, 2]
    for e in retries:
        assert e["op"] == "get"
        assert e["delay"] > 0
        assert "Transient" in e["error"]


def test_late_event_path_reconstructs_from_jsonl(tmp_path):
    """Acceptance: one sampled trace follows a late event end to end —
    ingest -> late write (I/O thread) and ingest -> watermark advance ->
    pipelined fold (worker thread) share the ingest span's trace id."""
    eng = _engine(tmp_path, trace_sample_rate=1.0,
                  pipelined_execution=True)
    eng.ingest(_batch(600, hi=40.0), now=1.0)
    eng.advance_watermark(50.0, now=2.0)
    assert eng.pipeline.drain(timeout=30.0)
    # late arrivals into already-expired windows
    eng.ingest(_batch(64, seed=3, hi=10.0), now=3.0)
    eng.poll(200.0)
    assert eng.pipeline.drain(timeout=30.0)
    assert eng.io.drain(timeout=30)
    eng.close()
    recs = [json.loads(l)
            for l in eng.tracer.export_jsonl().splitlines()]
    by_span = {r["span"]: r for r in recs}
    ingests = [r for r in recs if r["name"] == "ingest"
               and r["attrs"].get("late", 0) > 0]
    assert ingests, "no late ingest span"
    trace_id = ingests[-1]["trace"]
    family = [r for r in recs if r["trace"] == trace_id]
    names = {r["name"] for r in family}
    assert "io.late_write" in names        # persistence hop
    for r in family:
        if r["name"] == "io.late_write":
            assert by_span[r["parent"]]["name"] == "ingest"
            assert r["thread"] != by_span[r["parent"]]["thread"]


# ======================================================== observability
def test_observability_matches_legacy_surfaces(tmp_path):
    """Parity soak: the snapshot must agree with every legacy counter
    surface it replaced — same numbers, one call."""
    eng = _engine(tmp_path, breaker_error_threshold=4)
    for i in range(6):
        eng.ingest(_batch(200, seed=i, hi=40.0), now=float(i))
    eng.advance_watermark(50.0, now=7.0)
    eng.poll(8.0)
    eng.poll(60.0)
    assert eng.io.drain(timeout=30)
    snap = eng.observability()
    assert snap["engine"]["ingested"] == eng.metrics.ingested
    assert snap["engine"]["live_executions"] == \
        eng.metrics.live_executions
    assert snap["io"] == eng.io.stats.copy()
    assert snap["executor"] == eng.io.executor.stats.copy()
    assert snap["store"] == eng.store.stats.copy()
    assert snap["health"]["level"] == eng.health.level
    assert snap["trace"]["sample_rate"] == 0.0
    if eng.pool is not None:
        assert snap["pool"]["pool_slots"] == eng.pool.pool_slots
    assert "cache_size" in snap["fold"]
    eng.close()


def test_prometheus_export_format(tmp_path):
    eng = _engine(tmp_path)
    eng.ingest(_batch(64), now=1.0)
    eng.advance_watermark(10.0, now=2.0)
    eng.poll(3.0)
    text = eng.observability(export="prometheus")
    lines = text.splitlines()
    assert any(l.startswith("# TYPE aion_engine_ingested_total counter")
               for l in lines)
    assert any(l.startswith('aion_engine_ingested_total{tenant="default"}')
               for l in lines)
    # histograms expose cumulative buckets + sum/count
    assert any("aion_fold_round_seconds_bucket" in l and 'le="+Inf"' in l
               for l in lines)
    assert any(l.startswith("aion_fold_round_seconds_count") for l in lines)
    # json export parses and carries the same counter
    js = json.loads(eng.observability(export="json"))
    assert js['aion_engine_ingested{tenant="default"}'] == 64
    with pytest.raises(ValueError):
        eng.observability(export="xml")
    eng.close()


def test_pool_occupancy_via_registry_callback(tmp_path):
    eng = _engine(tmp_path)
    if eng.pool is None:
        eng.close()
        pytest.skip("no pool on this configuration")
    snap = json.loads(eng.observability(export="json"))
    assert snap["aion_pool_slots"] == eng.pool.pool_slots
    assert snap["aion_pool_free_slots"] == eng.pool.free_slots()
    eng.close()


def test_multitenant_observability_covers_everything(tmp_path):
    aion = AionConfig(block_size=32)
    mt = MultiTenantEngine(
        [TenantSpec(name="a", assigner=TumblingWindows(10.0),
                    operator=make_operator("average", 32, 1)),
         TenantSpec(name="b", assigner=TumblingWindows(10.0),
                    operator=make_operator("average", 32, 1))],
        spill_dir=tmp_path, aion=aion)
    mt.ingest("a", _batch(128, seed=1), now=1.0)
    mt.ingest("b", _batch(64, seed=2), now=1.0)
    mt.advance_watermark(20.0, now=2.0)
    mt.poll(3.0)
    snap = mt.observability()
    assert set(snap["tenants"]) == {"a", "b"}
    assert snap["tenants"]["a"]["engine"]["ingested"] == 128
    assert snap["tenants"]["b"]["engine"]["ingested"] == 64
    assert "tenant_fairness" in snap and "executor" in snap
    # per-tenant label children in ONE shared registry
    reg = snap["registry"]
    assert reg['aion_engine_ingested{tenant="a"}'] == 128
    assert reg['aion_engine_ingested{tenant="b"}'] == 64
    prom = mt.observability(export="prometheus")
    assert 'tenant="a"' in prom and 'tenant="b"' in prom
    mt.close()


def _spill_window(eng):
    """Push the first window's blocks through host to the log store, so
    a later stage or execution reads them back."""
    state = next(iter(eng.windows.values()))
    for blk in list(state.blocks):
        eng.io.destage_block_sync(blk)
    eng.io.spill_blocks_sync(list(state.blocks))
    return state


def _late_spilled_run(eng):
    """Live executions, a spilled window read back by a demand stage,
    then late events executed by a poll far ahead."""
    eng.ingest(_batch(600, hi=40.0), now=1.0)
    state = _spill_window(eng)
    assert eng.io.request_stage(state, demand=True).wait_checked(30.0)
    eng.advance_watermark(50.0, now=2.0)
    eng.ingest(_batch(64, seed=3, hi=10.0), now=3.0)
    eng.poll(200.0)
    assert eng.io.drain(timeout=30)


def test_tracing_overhead_disabled_is_free(tmp_path, monkeypatch):
    """With sampling and the profiler sink off the hot path must
    allocate nothing: every span handed out is THE NullSpan singleton,
    and a run through ingest, store reads, arena fills and late
    executions builds no span and opens no profiler annotation."""
    from repro.obs import trace as trace_mod
    made = []

    class CountingSpan(trace_mod.Span):
        __slots__ = ()

        def __init__(self, *a, **kw):
            made.append("span")
            super().__init__(*a, **kw)

    class CountingProfiledSpan(trace_mod.ProfiledSpan):
        __slots__ = ()

        def __init__(self, *a, **kw):
            made.append("profiled")
            super().__init__(*a, **kw)

    def counting_annotation(name):
        made.append(name)
        return trace_mod.TraceAnnotation(name)
    monkeypatch.setattr(trace_mod, "Span", CountingSpan)
    monkeypatch.setattr(trace_mod, "ProfiledSpan", CountingProfiledSpan)
    monkeypatch.setattr(trace_mod, "TraceAnnotation", counting_annotation)
    eng = _engine(tmp_path)
    assert eng.tracer.root("a") is NULL_SPAN
    assert eng.tracer.child(NULL_SPAN, "b") is NULL_SPAN
    assert eng.tracer.child(None, "c") is NULL_SPAN
    _late_spilled_run(eng)
    assert eng.metrics.late_executions > 0
    assert eng.store.stats["read_seconds"] > 0
    assert len(eng.metrics.executions) == 0     # records only when on
    eng.close()
    assert made == []


# ============================================ profiler sink (device clock)
def _profiler_session():
    import jax
    from jax._src.lib import _profiler
    jax.devices()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1           # user annotations only
    return _profiler.ProfilerSession(opts)


def _aion_events(session):
    """``(line, name, start_ns, duration_ns)`` of every ``aion.*`` host
    event of the stopped session; ``line`` is the index of the host
    plane's line (one line per thread)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(session.stop())
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("aion."):
                    out.append((i, ev.name, int(ev.start_ns),
                                int(ev.duration_ns)))
    return out


def test_profiler_sink_puts_sampled_spans_on_the_trace_clock():
    """Two sampled spans land in the profiler trace as ``aion.<name>``;
    the gap between their starts and each one's duration agree within
    1 ms between the JSON-lines export and the xplane."""
    import time
    tr = Tracer(sample_rate=1.0, profile=True)
    session = _profiler_session()
    with tr.root("first"):
        time.sleep(0.02)
    time.sleep(0.01)
    with tr.root("second"):
        time.sleep(0.03)
    events = {name: (start, dur)
              for _, name, start, dur in _aion_events(session)}
    recs = {r["name"]: r for r in
            (json.loads(l) for l in tr.export_jsonl().splitlines())}
    assert set(events) == {"aion.first", "aion.second"}
    gap_x = events["aion.second"][0] - events["aion.first"][0]
    gap_j = recs["second"]["t0"] - recs["first"]["t0"]
    assert gap_j > 0.03e9
    assert abs(gap_x - gap_j) < 1_000_000
    for name in ("first", "second"):
        assert abs(events["aion." + name][1] - recs[name]["dur"]) \
            < 1_000_000


def test_profiler_sink_io_task_span_on_the_executor_thread(tmp_path):
    """Under the sink with sampling off, spans are profiler annotations
    only (the ring stays empty); an I/O task span created at submit
    time on the ingest thread is annotated where the task runs, on the
    executor thread's line."""
    eng = _engine(tmp_path, profiler_annotations=True)
    session = _profiler_session()
    eng.ingest(_batch(600, hi=40.0), now=1.0)
    eng.advance_watermark(50.0, now=2.0)
    eng.ingest(_batch(64, seed=3, hi=10.0), now=3.0)   # late: io.late_write
    eng.poll(200.0)
    assert eng.io.drain(timeout=30)
    events = _aion_events(session)
    eng.close()
    assert eng.tracer.records() == []
    lines = {}
    for line, name, _, _ in events:
        lines.setdefault(name, set()).add(line)
    for name in ("aion.ingest", "aion.watermark_advance", "aion.poll",
                 "aion.execute_window", "aion.io.late_write"):
        assert name in lines, name
    assert lines["aion.io.late_write"].isdisjoint(lines["aion.ingest"])


@pytest.mark.parametrize("backend", ["log", "npz"])
def test_store_reads_and_fills_join_the_callers_trace(tmp_path, backend):
    """At sample rate 1.0 store reads and arena fills are children of
    the span their thread runs under, never traces of their own: a late
    execution's reads of its spilled blocks share its trace id."""
    eng = _engine(tmp_path, trace_sample_rate=1.0, store_backend=backend)
    eng.ingest(_batch(200, hi=9.0), now=1.0)      # one window, [0, 10)
    eng.advance_watermark(20.0, now=2.0)
    _spill_window(eng)
    eng.tracer.clear()
    eng.ingest(_batch(32, seed=5, hi=9.0), now=3.0)
    eng.poll(200.0)
    assert eng.io.drain(timeout=30)
    recs = eng.tracer.records()
    eng.close()
    assert eng.metrics.late_executions == 1
    by_id = {r["span"]: r for r in recs}
    inner = [r for r in recs if r["name"] in ("store.read", "pool.fill")]
    assert {r["name"] for r in inner} >= {"store.read"}
    assert all(r["parent"] in by_id for r in inner)
    execs = [r for r in recs if r["name"] == "execute_window"]
    assert len(execs) == 1 and execs[0]["attrs"]["late"] is True
    trace = execs[0]["trace"]
    reads = [r for r in inner if r["name"] == "store.read"]
    assert reads and all(r["trace"] == trace for r in reads)
    assert execs[0]["attrs"]["storage"] > 0
    # roots are the engine's entry points, one trace each
    roots = {r["name"] for r in recs if r["parent"] is None}
    assert roots <= {"ingest", "poll", "watermark_advance"}


def test_inner_span_without_an_entered_span_is_never_sampled():
    tr = Tracer(sample_rate=1.0)
    assert tr.inner("store.read") is NULL_SPAN
    with tr.root("poll") as outer:
        with tr.inner("store.read") as read:
            assert read.trace_id == outer.trace_id
            assert tr.inner("pool.fill").parent_id == read.span_id
        assert tr.inner("pool.fill").parent_id == outer.span_id
    assert tr.inner("pool.fill") is NULL_SPAN
    profiled = Tracer(profile=True)
    assert not profiled.inner("store.read").sampled
    assert Tracer().inner("store.read") is NULL_SPAN


# ==================================== store, arena and execution counters
def test_store_read_and_pool_fill_seconds(tmp_path):
    """Reading a spilled block back is timed into the store's
    ``read_seconds``, its arena fill into the pool's ``fill_seconds``."""
    eng = _engine(tmp_path)
    assert eng.pool is not None
    eng.ingest(_batch(256), now=1.0)
    state = _spill_window(eng)
    assert eng.store.stats["read_seconds"] == 0
    fills0 = eng.pool.stats["writes"]
    assert eng.io.request_stage(state, demand=True).wait_checked(30.0)
    assert eng.io.drain(timeout=30)
    assert eng.store.stats["bytes_read"] > 0
    assert eng.store.stats["read_seconds"] > 0
    assert eng.pool.stats["writes"] > fills0
    assert eng.pool.stats["fill_seconds"] > 0
    assert "read_seconds" in eng.observability()["store"]
    eng.close()


def test_late_execution_leaves_one_record_and_the_series_stays_capped(
        tmp_path):
    """With tracing on, a single-window late execution appends one
    record with ``late``, its path and its blocks by tier; the series
    keeps its cap."""
    eng = _engine(tmp_path, metrics_series_max=8,
                  profiler_annotations=True)
    eng.ingest(_batch(200, hi=9.0), now=1.0)      # one window, [0, 10)
    eng.advance_watermark(20.0, now=2.0)
    live = list(eng.metrics.executions)
    assert len(live) == 1 and live[0]["late"] is False
    state = _spill_window(eng)
    eng.ingest(_batch(32, seed=5, hi=9.0), now=3.0)
    n0 = len(eng.metrics.executions)
    eng.poll(200.0)
    recs = list(eng.metrics.executions)[n0:]
    assert len(recs) == 1
    rec = recs[0]
    assert rec["late"] is True and rec["path"] == "single"
    assert rec["window"] == 0.0
    assert rec["t1"] >= rec["t0"] > 0
    assert set(rec["blocks"]) == {"device", "host", "storage"}
    assert sum(rec["blocks"].values()) == len(state.blocks)
    assert rec["blocks"]["storage"] > 0
    wid = next(iter(eng.windows))
    for _ in range(40):
        eng.execute_window(wid, 300.0, late=True)
    assert len(eng.metrics.executions) <= 8
    assert eng.metrics.executions[-1]["late"] is True
    eng.close()
