"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers
the per-layer metrics read.

The traced window is the span of the harness's own ``bench.step``
annotations on the host. Within it:

- busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:`` plane), averaged
  over the devices that ran any;
- kernel time: the summed device durations of the operations whose kind
  (a TPU trace names each by its HLO text, ``%<kind>.<n> = ...``) starts
  with one of the given prefixes;
- idle gaps: each gap in the busy union, charged to the innermost host
  annotation (``bench.*`` or ``aion.*``) covering its midpoint.

Only ``jax.profiler.ProfileData`` is used, so the reduction runs
wherever JAX does, a recorded trace included.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Iterable, List, Optional, Tuple

_OPS_LINE = "XLA Ops"
_HOST_PREFIXES = ("bench.", "aion.")
_STEP_SPAN = "bench.step"


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _op_name(name: str) -> str:
    """The kind of a device operation: a TPU trace names each by its HLO
    text, ``%fusion.12 = f32[2]{0} fusion(...)``; ``fusion.12`` and
    ``fusion.3`` are one kind."""
    return re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))


def _cover(host, starts, t: int) -> str:
    """The innermost host span covering ``t``: the latest-starting one
    that has not ended (spans nest within one thread)."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        if host[i][1] > t:
            return host[i][2]
        i -= 1
    return "outside any bench span"


class Session:
    """A profiler session whose trace stays in memory: no trace files
    are written, and none of the export that ``jax.profiler.stop_trace``
    does, which takes minutes for a trace of a whole window."""

    def __init__(self):
        import jax
        from jax._src.lib import _profiler
        jax.devices()           # the TPU tracer needs the backend up
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # user annotations (bench.* and aion.*) but not the runtime's
        # own host events, which slow every call
        opts.host_tracer_level = 1
        self._sess = _profiler.ProfilerSession(opts)

    def stop(self):
        from jax.profiler import ProfileData
        return ProfileData.from_serialized_xspace(self._sess.stop())


def reduce(trace, kernel_prefixes: Iterable[str],
           top: int = 10) -> Optional[dict]:
    """Busy, window, kernel and breakdown numbers of one trace (a
    ``ProfileData`` or the path of an ``.xplane.pb``), or None when the
    trace holds no device operation inside the window."""
    from jax.profiler import ProfileData
    pd = trace if isinstance(trace, ProfileData) \
        else ProfileData.from_file(str(trace))
    kernels = tuple(kernel_prefixes)
    host: List[Tuple[int, int, str]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(_HOST_PREFIXES):
                        # aion.fold_round[n] -> aion.fold_round
                        host.append((int(ev.start_ns), int(ev.end_ns),
                                     re.sub(r"\[\d+\]$", "", ev.name)))
        elif plane.name.startswith("/device:"):
            ops = [ev for line in plane.lines if line.name == _OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append(ops)
    steps = [(s, e) for s, e, n in host if n == _STEP_SPAN]
    if not devices or not steps:
        return None
    lo = min(s for s, _ in steps)
    hi = max(e for _, e in steps)

    busy_ns = []
    kernel_ns = 0
    by_op: collections.Counter = collections.Counter()
    gaps: List[Tuple[int, int]] = []
    for ops in devices:
        spans = []
        by_name: collections.Counter = collections.Counter()
        for ev in ops:
            s = max(int(ev.start_ns), lo)
            e = min(int(ev.end_ns), hi)
            if e > s:
                spans.append((s, e))
                by_name[ev.name] += e - s
        for name, t in by_name.items():
            kind = _op_name(name)
            by_op[kind] += t
            if kind.startswith(kernels):
                kernel_ns += t
        merged = _union(spans)
        busy_ns.append(sum(e - s for s, e in merged))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1])
                 for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    if not any(busy_ns):
        return None

    host.sort()
    starts = [hs for hs, _, _ in host]
    idle = collections.Counter()
    for s, e in gaps:
        idle[_cover(host, starts, (s + e) // 2)] += e - s
    n_dev = len(devices)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n_dev / 1e9,
        "kernel_s": kernel_ns / n_dev / 1e9,
        "devices": n_dev,
        "device_ops": [[n, t / n_dev / 1e9]
                       for n, t in by_op.most_common(top)],
        "idle_gaps": [[n, t / n_dev / 1e9]
                      for n, t in idle.most_common(top)],
    }
