"""From the profiler's ``.xplane.pb`` to numbers: the union of the device's
busy intervals, self time by operation, and the longest idle gaps labelled
by the benchmark's own host span that covers them.

What the recording looks like (one v5e chip, jax 0.9.0, read by hand in
PR 24): a plane ``/device:TPU:<n>`` per chip whose line ``XLA Ops`` holds
every operation — parents such as ``%while.23`` as well as the operations
inside them, so a total by name has to be SELF time; a plane ``/host:CPU``
whose thread lines hold the ``TraceAnnotation`` spans (``bench.fit``,
``bench.between_fits``). Both count nanoseconds from the start of the
profile, so a device gap can be laid over a host span.

The arithmetic works on plain ``(name, start_ns, duration_ns)`` tuples and
is checked against ``fixtures/small_trace.json`` by the tests.
"""

from __future__ import annotations

import glob
import os
import shutil
from typing import Any, Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.fit"


def short_name(hlo: str) -> str:
    """``%cosine_features.4 = f32[...] custom-call(...)`` -> ``cosine_features.4``."""
    return hlo.split(" = ", 1)[0].lstrip("%")[:64]


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of ``(start, end)`` intervals."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if min(b, hi) > max(a, lo)]


def self_ns_by_name(events: Sequence[Event]) -> Dict[str, float]:
    """Each event's duration less what the events nested inside it cover
    (a ``while`` holds its body's operations), summed by name."""
    totals: Dict[str, float] = {}
    stack: List[List[Any]] = []  # [name, end_ns, self_ns]

    def close() -> None:
        name, _, self_ns = stack.pop()
        totals[name] = totals.get(name, 0.0) + max(self_ns, 0.0)

    for name, start, duration in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][1]:
            close()
        if stack:
            stack[-1][2] -= duration
        stack.append([name, start + duration, duration])
    while stack:
        close()
    return totals


def idle_gaps(busy: Sequence[Tuple[float, float]], window: Tuple[float, float],
              spans: Sequence[Event]) -> List[Tuple[str, float]]:
    """Gaps of the window that no busy interval covers, longest first, each
    labelled by the host span over its midpoint (``unspanned`` if none)."""
    gaps, cursor = [], window[0]
    for start, end in clip(busy, *window):
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if window[1] > cursor:
        gaps.append((cursor, window[1]))
    labelled = []
    for start, end in gaps:
        mid = (start + end) / 2
        # the innermost span over the midpoint: the latest to start
        over = [s for s in spans if s[1] <= mid < s[1] + s[2]]
        label = max(over, key=lambda s: s[1])[0] if over else "unspanned"
        labelled.append((label, end - start))
    return sorted(labelled, key=lambda g: -g[1])


def reduce_events(device_events: Sequence[Sequence[Event]],
                  spans: Sequence[Event]) -> Dict[str, Any]:
    """The summary ``run.py`` and the readers use. ``device_events`` holds
    one list per chip; busy time is averaged over the chips. The window is
    the extent of the ``bench.fit`` spans (the whole trace without them)."""
    fits = [s for s in spans if s[0] == WINDOW_SPAN]
    everything = fits or [e for chip in device_events for e in chip]
    window = (min(e[1] for e in everything), max(e[1] + e[2] for e in everything))
    busy_ns, op_ns, gaps = 0.0, {}, []
    for chip in device_events:
        busy = clip(merge([(s, s + d) for _, s, d in chip]), *window)
        busy_ns += sum(b - a for a, b in busy)
        for name, ns in self_ns_by_name(chip).items():
            op_ns[name] = op_ns.get(name, 0.0) + ns
        gaps += idle_gaps(busy, window, spans)
    chips = max(len(device_events), 1)
    by_time = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return {
        "busy_s": busy_ns / chips / 1e9,
        "window_s": (window[1] - window[0]) / 1e9,
        "op_seconds": {name: ns / chips / 1e9 for name, ns in op_ns.items()},
        "device_ops": [[name, ns / chips / 1e9] for name, ns in by_time],
        "idle_gaps": [[label, ns / 1e9] for label, ns in sorted(gaps, key=lambda g: -g[1])],
    }


def read_xplane(path: str) -> Tuple[List[List[Event]], List[Event]]:
    """(operations per device plane, the benchmark's host spans)."""
    import jax

    device_events: List[List[Event]] = []
    spans: List[Event] = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_events.append([(short_name(e.name), e.start_ns, e.duration_ns)
                                          for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return device_events, spans


def summarize(trace_dir: str, remove: bool = False) -> Dict[str, Any]:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    try:
        device_events, spans = read_xplane(sorted(paths)[-1])
    finally:
        if remove:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if not any(device_events):
        raise RuntimeError("the trace holds no operation on a device")
    return reduce_events(device_events, spans)
