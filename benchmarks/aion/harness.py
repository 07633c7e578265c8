"""The benchmark's run of one cell: set-up, measured window, check.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name in ``BENCHMARK.json``:

    configs/<config>.json     deployment: sizes, budgets, guarantees,
                              windows (``window_s``, optional
                              ``slide_s``), optional ``fold_kernels``
    reference/<operator>.py   plain float64 reference of the operator
    events/<operator>.py      ``draw(rng, n, config)``: keys and values
    traffic/<traffic>.json    lateness model, loop and rate
    metrics/<metric>.py       ``read(rec)`` -> number or None

A configuration's windows hop: ``window_s`` long, one starting every
``slide_s`` (``Windows``); without ``slide_s`` they are tumbling. Its
``fold_kernels`` are the op-name prefixes whose device time the trace
reduction sums (``FOLD_KERNELS`` without it). ``counters`` carries
every numeric counter of the engine, its pool and its store, so a
metric file reads a new mechanism's counter by name.

The window drives ``StreamEngine.ingest(batch, now)``, then
``advance_watermark(now, now)``, then ``poll(now)`` once per step, where
one step is ``step_s`` of stream time. The engine's clock ``now`` is
stream time; the wall clock only schedules the steps (open loop) or
follows them (closed loop). The program is given only the batches.
"""
from __future__ import annotations

import collections
import importlib.util
import json
import queue
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]
GIB = float(1 << 30)

#: the fold layer's Pallas kernels on the device: a TPU trace names each
#: ``pallas_call`` after the entry point of ``repro.kernels.
#: segment_aggregate`` that made it (``segment_aggregate_batched``,
#: ``segment_aggregate_block_table_splitk``, ...)
FOLD_KERNELS = ("segment_aggregate",)


def fold_kernels(config: dict) -> Tuple[str, ...]:
    """The op-name prefixes of the configuration's kernels."""
    return tuple(config.get("fold_kernels", FOLD_KERNELS))


@dataclass(frozen=True)
class Windows:
    """Hop windows: each ``size`` seconds long, one starting at every
    multiple of ``slide``; tumbling where ``slide == size``. Written
    from those semantics, apart from the program's assigners."""
    size: float
    slide: float

    def __post_init__(self):
        if not 0 < self.slide <= self.size:
            raise ValueError(f"a slide of {self.slide} s for windows of "
                             f"{self.size} s: it must be in (0, size]")

    @classmethod
    def of(cls, config: dict) -> "Windows":
        size = float(config["window_s"])
        return cls(size, float(config.get("slide_s", size)))

    @property
    def tumbling(self) -> bool:
        return self.slide == self.size

    def assign(self, ts: np.ndarray) -> List[Tuple[float, np.ndarray]]:
        """``[(start, mask)]`` in order of start: an event at ``ts`` is in
        every window whose start is a multiple of ``slide`` with
        ``start <= ts < start + size``. The window of the slide an event
        falls in is ``floor(ts / slide) * slide``, and the k-th before it
        starts ``k * slide`` earlier."""
        ts = np.asarray(ts)
        base = np.floor(ts / self.slide) * self.slide
        out: Dict[float, np.ndarray] = {}
        for k in range(int(np.ceil(self.size / self.slide))):
            starts = base - k * self.slide
            inside = ts < starts + self.size
            for s in np.unique(starts[inside]):
                mask = inside & (starts == s)
                s = float(s)
                out[s] = out[s] | mask if s in out else mask
        return sorted(out.items(), key=lambda kv: kv[0])


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "aion_bench_" + path.stem.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(rec)`` of ``metrics/<metric>.py``."""
    return load_module(HERE / "metrics" / f"{metric}.py").read


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    reference: object
    events: object


def load_cell(name: str, bench: Optional[dict] = None,
              root: Path = HERE) -> Cell:
    """The cell named ``name`` with its configuration, traffic, metrics
    reference and events, all found by name under ``root``."""
    if bench is None:
        bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    config = json.loads((root / "configs" / f"{w['config']}.json")
                        .read_text())
    traffic = json.loads((root / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    op = config["operator"]
    return Cell(name, config, traffic, e2e, per_layer,
                load_module(root / "reference" / f"{op}.py"),
                load_module(root / "events" / f"{op}.py"))


class CompileLog:
    """Programs made ready after warm-up: every lowering to a backend
    program, whether it then compiles or loads from the persistent
    cache, and the backend compilations among them."""

    def __init__(self):
        import jax
        self.warm = False
        self.lowered = 0
        self.compiled = 0
        self.names: collections.Counter = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if not self.warm:
            return
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1
            self.names[kw.get("fun_name", "?")] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


@dataclass
class Step:
    """One step: what was sent, when, and what came out."""
    index: int
    now: float
    keys: np.ndarray
    ts: np.ndarray
    vals: np.ndarray              # the lanes the reference reads
    window: bool = False          # inside the measured window
    due: float = 0.0              # wall clock, window steps only
    sent: float = 0.0
    done: float = 0.0
    ingest_s: float = 0.0
    control_s: float = 0.0        # advance_watermark + poll
    round_s: float = 0.0          # exec_seconds delta of the step
    emitted: list = field(default_factory=list)   # (wid, result, wall)
    purged: list = field(default_factory=list)
    late_by_window: Dict[float, int] = field(default_factory=dict)


class Producer:
    """Draws the stream's batches from the seed on a thread of its own,
    ahead of the schedule, so that drawing is outside the engine's
    timing. Batches come out in step order whatever the timing, so a
    seed fixes every step's events."""

    def __init__(self, gen, per_step: int, step_s: float, depth: int = 6):
        self.gen = gen
        self.per_step = per_step
        self.step_s = step_s
        self.q: queue.Queue = queue.Queue(maxsize=depth)
        self.stop = threading.Event()
        self.error: Optional[BaseException] = None
        self.thread = threading.Thread(target=self._run, daemon=True,
                                       name="bench-producer")
        self.thread.start()

    def _run(self):
        k = 0
        try:
            while not self.stop.is_set():
                now = k * self.step_s
                item = (now, self.gen.batch(self.per_step, now))
                while not self.stop.is_set():
                    try:
                        self.q.put(item, timeout=0.1)
                        break
                    except queue.Full:
                        continue
                k += 1
        except BaseException as e:      # surfaced by next()
            self.error = e

    def next(self):
        while True:
            if self.error is not None:
                raise RuntimeError("traffic generator failed") \
                    from self.error
            try:
                return self.q.get(timeout=1.0)
            except queue.Empty:
                continue

    def close(self):
        self.stop.set()
        self.thread.join(timeout=30)


def make_engine(config: dict, store_dir: Path, annotate: bool):
    from repro.configs.base import AionConfig
    from repro.core import SlidingWindows, StreamEngine, TumblingWindows
    from repro.core.operators import make_operator
    g = config["guarantees"]
    aion = AionConfig(block_size=config["block_size"],
                      pool_slots=config["pool_slots"],
                      splitk_chunk_rows=config["splitk_chunk_rows"],
                      cleanup_coverage=g["cleanup_coverage"],
                      max_staleness=g["max_staleness"],
                      store_backend=config["store"],
                      wal_coalesce_commits=g["wal_group_commit"],
                      profiler_annotations=annotate)
    op = make_operator(config["operator"], aion.block_size,
                       config["value_width"], **config["operator_args"])
    w = Windows.of(config)
    return StreamEngine(
        assigner=(TumblingWindows(w.size) if w.tumbling
                  else SlidingWindows(w.size, w.slide)),
        operator=op, aion=aion, value_width=config["value_width"],
        device_budget_bytes=config["device_budget_bytes"],
        host_budget_bytes=config["host_budget_bytes"],
        spill_dir=store_dir)


def _numeric(prefix: str, items) -> dict:
    return {f"{prefix}.{k}": v for k, v in items
            if isinstance(v, (int, float)) and not isinstance(v, bool)}


def counters(eng) -> dict:
    """The engine's counters now: the named ones the metric files of
    the first cells read, then every numeric scalar of the engine's
    metrics, its pool's and its store's stats as ``metrics.<name>``,
    ``pool.<name>`` and ``store.<name>``."""
    m = eng.metrics
    store = eng.store.stats if eng.store is not None else {}
    out = {
        "ingested": m.ingested, "ingested_late": m.ingested_late,
        "exec_seconds": m.exec_seconds,
        "live_executions": m.live_executions,
        "late_executions": m.late_executions,
        "batch_executions": m.batch_executions,
        "batched_windows": m.batched_windows,
        "pooled_rows": m.pooled_rows, "fallback_rows": m.fallback_rows,
        "demand_pool_fills": m.demand_pool_fills,
        "splitk_launches": m.splitk_launches,
        "spilled_blocks": eng.io.stats["spilled_blocks"],
        "store_bytes_read": store.get("bytes_read", 0),
        "store_bytes_written": store.get("bytes_written", 0),
    }
    out.update(_numeric("metrics", m.scalars().items()))
    if eng.pool is not None:
        out.update(_numeric("pool", eng.pool.stats.items()))
    out.update(_numeric("store", store.items()))
    return out


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


def _annotation(on: bool, name: str):
    if on:
        import jax
        return jax.profiler.TraceAnnotation(name)
    import contextlib
    return contextlib.nullcontext()


class Driver:
    """Drives one engine through the stream and records what it sees."""

    def __init__(self, eng, windows: Windows, ref_cols: int):
        self.eng = eng
        self.windows = windows
        self.ref_cols = ref_cols
        self.steps: List[Step] = []
        self.seen: dict = {}
        self.wm = -np.inf
        self.annotate = False

    def _emissions(self, step: Step) -> None:
        t = time.perf_counter()
        for wid, res in self.eng.results.items():
            if self.seen.get(wid) is not res:
                self.seen[wid] = res
                step.emitted.append((wid, res, t))

    def step(self, now: float, batch, window: bool = False,
             due: float = 0.0) -> Step:
        from repro.core.events import EventBatch
        keys, ts, vals = batch
        st = Step(len(self.steps), now, keys, ts,
                  np.ascontiguousarray(vals[:, :self.ref_cols]),
                  window=window, due=due)
        if window:
            # late as the engine sees it: a window that closed already
            size = self.windows.size
            st.late_by_window = {
                s: int(mask.sum()) for s, mask in self.windows.assign(ts)
                if s + size <= self.wm}
        eng = self.eng
        ann = self.annotate
        ex0 = eng.metrics.exec_seconds
        with _annotation(ann, "bench.step"):
            st.sent = time.perf_counter()
            with _annotation(ann, "bench.ingest"):
                eng.ingest(EventBatch(keys, ts, vals), now)
            t1 = time.perf_counter()
            with _annotation(ann, "bench.advance_watermark"):
                eng.advance_watermark(now, now)
            self.wm = max(self.wm, now)
            self._emissions(st)
            # only poll purges; a window made by this step's ingest may
            # go in the same poll
            live = set(eng.windows)
            with _annotation(ann, "bench.poll"):
                eng.poll(now)
            self._emissions(st)
            st.done = time.perf_counter()
        st.ingest_s = t1 - st.sent
        st.control_s = st.done - t1
        st.round_s = eng.metrics.exec_seconds - ex0
        st.purged = [w for w in live - set(eng.windows)]
        self.steps.append(st)
        return st

    def flush(self, now: float) -> Step:
        """After the window: every planned re-execution falls due in one
        poll, so every window's last answer covers all its events."""
        st = Step(len(self.steps), now, np.zeros(0, np.int32),
                  np.zeros(0), np.zeros((0, self.ref_cols), np.float32))
        live = set(self.eng.windows)
        self.eng.poll(now)
        self._emissions(st)
        st.purged = [w for w in live - set(self.eng.windows)]
        self.steps.append(st)
        return st


def check(steps: List[Step], ref, windows: Windows, num_keys: int,
          watermark: float, control=None) -> dict:
    """Replay the stream through the reference and compare every
    emission of the window and of the flush after it.

    Returns ``numbers`` (the worst of each compared number), ``attempted``
    (answers compared), ``failed`` (answers over a limit, or never
    given) and ``missing``: windows closed by ``watermark`` that took
    events in the window and whose last answer does not cover them.

    With ``control`` (keyword arguments of the reference's ``Window``:
    ``dtype``, ``values``), the reference computed so takes the
    program's place at each of its answers."""
    state: Dict[float, object] = {}
    shadow: Dict[float, object] = {}
    last_emit: Dict[float, int] = {}
    last_event: Dict[float, int] = {}
    numbers = {k: 0 for k in ref.LIMITS}
    attempted = failed = 0
    first_window = next((s.index for s in steps if s.window), None)
    for st in steps:
        if len(st.ts):
            for s, sel in windows.assign(st.ts):
                if s not in state:
                    state[s] = ref.Window(num_keys)
                    if control is not None:
                        shadow[s] = ref.Window(num_keys, **control)
                state[s].add(st.keys[sel], st.vals[sel])
                if control is not None:
                    shadow[s].add(st.keys[sel], st.vals[sel])
                last_event[s] = st.index
        compare_here = first_window is not None \
            and st.index >= first_window
        for wid, res, _ in st.emitted:
            s = float(wid.start)
            last_emit[s] = st.index
            if not compare_here:
                continue
            want = state[s] if s in state else ref.Window(num_keys)
            if control is not None:
                res = (shadow[s] if s in shadow
                       else ref.Window(num_keys, **control)).result()
            nums = ref.compare(res, want.result())
            attempted += 1
            bad = False
            for k, v in nums.items():
                numbers[k] = max(numbers[k], v)
                bad |= v > ref.LIMITS[k]
            failed += bad
        for wid in st.purged:
            s = float(wid.start)
            state.pop(s, None)
            shadow.pop(s, None)
            last_event.pop(s, None)
    missing = 0
    if first_window is not None:
        for s, k in last_event.items():
            if s + windows.size <= watermark and k >= first_window \
                    and last_emit.get(s, -1) < k:
                missing += 1
    return {"numbers": numbers, "attempted": attempted + missing,
            "failed": failed + missing, "missing": missing}


def staleness(steps: List[Step], t_end: float) -> dict:
    """Per late event of the window: its due time to the wall time of
    the first answer of its window given at or after its step. Events
    with no such answer by the window's end count at their age then."""
    emits: Dict[float, List] = collections.defaultdict(list)
    for st in steps:
        for wid, _, t in st.emitted:
            emits[float(wid.start)].append((st.index, t))
    ages, weights = [], []
    unanswered = 0
    for st in steps:
        if not st.window:
            continue
        for s, n in st.late_by_window.items():
            t = next((t for k, t in emits.get(s, ()) if k >= st.index
                      and t <= t_end), None)
            if t is None:
                unanswered += n
                t = t_end
            ages.append(t - st.due)
            weights.append(n)
    return {"ages": np.asarray(ages), "weights": np.asarray(weights),
            "unanswered": unanswered}


def weighted_quantile(values: np.ndarray, weights: np.ndarray,
                      q: float) -> Optional[float]:
    """The smallest value at or above which lies a share 1 - q of the
    weight (the inverse of the weighted empirical distribution)."""
    if not len(values) or weights.sum() <= 0:
        return None
    order = np.argsort(values, kind="stable")
    cum = np.cumsum(weights[order])
    i = int(np.searchsorted(cum, q * cum[-1], side="left"))
    return float(values[order][min(i, len(values) - 1)])
