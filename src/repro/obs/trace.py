"""Structured tracing with explicit parent handoff across threads.

Spans carry ``(trace_id, span_id, parent_id)``; a parent span object is
passed *explicitly* to :meth:`Tracer.child` — never via thread-locals — so
a fold round executed on the pipeline worker can parent to the
watermark-advance span created on the caller thread, and an I/O task span
can parent to whichever engine span submitted it.

Sampling happens once, at the root: :meth:`Tracer.root` flips a seeded
coin at ``sample_rate``; children inherit the decision from their parent.
Sampled spans land in a bounded ring buffer (oldest dropped) and export
as JSON-lines via :meth:`Tracer.export_jsonl`; ``t0`` is the span's
creation in ``time.time_ns()``, and ``dur`` and each event's ``t`` are
nanoseconds from it.

The profiler sink (``profile=True``, ``AionConfig.profiler_annotations``)
makes every span the program opens, sampled or not, a
``jax.profiler.TraceAnnotation`` named ``aion.<name>`` while it is
entered (``with span:``), so engine spans sit in a profiler trace on the
device's clock. The annotation opens and closes on the entering thread:
an I/O task span created at submit time is entered only where the task
runs. Unsampled spans under the sink are :class:`ProfiledSpan`, which
record nothing in the ring.

Work that does not know its caller (store reads, arena fills) opens
:meth:`Tracer.inner`: a child of the span its thread has entered, and
never a root of its own, so it neither flips the sampling coin nor
starts a trace.

With the sink off, unsampled (and all, when ``sample_rate <= 0``) spans
are the module singleton :data:`NULL_SPAN`, whose every method is a
no-op — the hot-path cost of disabled tracing is one attribute read and
one predictable branch.
"""
from __future__ import annotations

import itertools
import json
import random
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

__all__ = ["Span", "NullSpan", "NULL_SPAN", "ProfiledSpan", "Tracer"]


def _annotate(name: str):
    ann = TraceAnnotation("aion." + name)
    ann.__enter__()
    return ann


class NullSpan:
    """No-op span; stands in for every unsampled span."""

    __slots__ = ()
    sampled = False
    trace_id = 0
    span_id = 0

    def event(self, name: str, **attrs) -> None:
        pass

    def set(self, **attrs) -> None:
        pass

    def end(self, **attrs) -> None:
        pass

    def __enter__(self) -> "NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def __bool__(self) -> bool:
        return False


NULL_SPAN = NullSpan()


class ProfiledSpan(NullSpan):
    """Unsampled span under the profiler sink: a profiler annotation
    while entered, nothing in the ring."""

    __slots__ = ("_tracer", "name", "_ann", "_outer")

    def __init__(self, tracer: "Tracer", name: str) -> None:
        self._tracer = tracer
        self.name = name
        self._ann = None
        self._outer = None

    def end(self, **attrs) -> None:
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)

    def __enter__(self) -> "ProfiledSpan":
        self._outer = self._tracer._push(self)
        self._ann = _annotate(self.name)
        return self

    def __exit__(self, *exc) -> None:
        self.end()
        self._tracer._local.span = self._outer


class Span:
    """A sampled span. Mutate only from the thread currently running it;
    hand it to another thread as a *parent* (read-only) freely."""

    __slots__ = ("_tracer", "name", "trace_id", "span_id", "parent_id",
                 "t0", "attrs", "events", "thread", "_ended", "_ann",
                 "_outer")
    sampled = True

    def __init__(self, tracer: "Tracer", name: str, trace_id: int,
                 span_id: int, parent_id: Optional[int],
                 attrs: Dict[str, object]) -> None:
        self._tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.t0 = time.time_ns()
        self.attrs = attrs
        self.events: List[Dict[str, object]] = []
        self.thread = threading.current_thread().name
        self._ended = False
        self._ann = None
        self._outer = None

    def event(self, name: str, **attrs) -> None:
        rec: Dict[str, object] = {"name": name,
                                  "t": time.time_ns() - self.t0}
        if attrs:
            rec.update(attrs)
        self.events.append(rec)

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def end(self, **attrs) -> None:
        if self._ended:
            return
        self._ended = True
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        # re-stamp with the finishing thread: task spans are created on
        # the submitter thread but run (and end) on the executor, and the
        # executing thread is the one cross-thread reconstruction needs
        self.thread = threading.current_thread().name
        if attrs:
            self.attrs.update(attrs)
        self._tracer._finish(self)

    def __enter__(self) -> "Span":
        self._outer = self._tracer._push(self)
        if self._tracer.profile:
            self._ann = _annotate(self.name)
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self.end()
        self._tracer._local.span = self._outer

    def __bool__(self) -> bool:
        return True


class Tracer:
    """Span factory + bounded ring of finished span records.

    ``sample_rate`` in [0, 1] gates *root* spans only; the decision then
    flows down the parent chain. ``seed`` makes sampling reproducible.
    ``profile`` turns on the profiler sink (module docstring).
    """

    def __init__(self, sample_rate: float = 0.0, capacity: int = 4096,
                 seed: int = 0, profile: bool = False) -> None:
        self.sample_rate = float(sample_rate)
        self.profile = bool(profile)
        self._on = self.sample_rate > 0.0 or self.profile
        self.capacity = int(capacity)
        self._ring: deque = deque(maxlen=max(1, self.capacity))
        self._rng = random.Random(seed)
        self._ids = itertools.count(1)
        self._lock = threading.Lock()
        # per thread: the innermost span entered there (``inner``)
        self._local = threading.local()
        self.spans_started = 0
        self.spans_finished = 0
        self.spans_dropped = 0

    @property
    def enabled(self) -> bool:
        return self._on

    # -- span creation ----------------------------------------------------
    def root(self, name: str, **attrs):
        """Start a new trace; samples at ``sample_rate``."""
        if not self._on:
            return NULL_SPAN
        if self.sample_rate > 0.0:
            with self._lock:
                sampled = self.sample_rate >= 1.0 \
                    or self._rng.random() < self.sample_rate
                if sampled:
                    trace_id = span_id = next(self._ids)
                    self.spans_started += 1
            if sampled:
                return Span(self, name, trace_id, span_id, None,
                            dict(attrs))
        return ProfiledSpan(self, name) if self.profile else NULL_SPAN

    def child(self, parent, name: str, **attrs):
        """Continue ``parent``'s trace; unsampled when the parent is
        (NULL, or a ProfiledSpan under the profiler sink)."""
        if not self._on:
            return NULL_SPAN
        if parent is not None and parent.sampled:
            with self._lock:
                span_id = next(self._ids)
                self.spans_started += 1
            return Span(self, name, parent.trace_id, span_id,
                        parent.span_id, dict(attrs))
        return ProfiledSpan(self, name) if self.profile else NULL_SPAN

    def inner(self, name: str, **attrs):
        """A child of the innermost span this thread has entered; with
        none entered, unsampled (a ProfiledSpan under the sink), never
        a root."""
        if not self._on:
            return NULL_SPAN
        return self.child(getattr(self._local, "span", None), name,
                          **attrs)

    def _push(self, span):
        """Make ``span`` this thread's innermost; returns the outer one,
        which the span restores on exit."""
        outer = getattr(self._local, "span", None)
        self._local.span = span
        return outer

    # -- ring -------------------------------------------------------------
    def _finish(self, span: Span) -> None:
        rec = {
            "name": span.name,
            "trace": span.trace_id,
            "span": span.span_id,
            "parent": span.parent_id,
            "t0": span.t0,
            "dur": time.time_ns() - span.t0,
            "thread": span.thread,
            "attrs": span.attrs,
            "events": span.events,
        }
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.spans_dropped += 1
            self._ring.append(rec)
            self.spans_finished += 1

    def records(self) -> List[Dict[str, object]]:
        with self._lock:
            return list(self._ring)

    def export_jsonl(self) -> str:
        return "\n".join(json.dumps(rec, default=str)
                         for rec in self.records())

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "sample_rate": self.sample_rate,
                "profile": self.profile,
                "spans_started": self.spans_started,
                "spans_finished": self.spans_finished,
                "spans_dropped": self.spans_dropped,
                "ring_len": len(self._ring),
                "ring_capacity": self.capacity,
            }
