"""The program's own spans in a `jax.profiler` trace of the measured window.

With `repro.obs` in profiler mode (`obs.trace_on_profiler(True)`) every span
of the program is a host event on `/host:CPU`, one line per thread, on the
clock of the device's programs, its numeric attributes as the event's
stats.  `reduce_spans` reads them beside the `bench.*` annotations the
benchmark wraps its own calls in:

  * `spans`: per span name, the spans that start inside `bench.window`:
    their count, total and self seconds (self: the duration less what the
    span's children on the same thread line cover), and the sums of their
    numeric stats;
  * `engine_host_s`: the host time of the engine's flushes, the duration of
    `engine.run_compiled` less its same-thread `engine.fetch` (waits for
    the device) and `synopsis.fit` descendants;
  * `idle_by_span`: each idle gap of the device (no program running)
    charged to the span, of the program or of the benchmark, that is
    innermost on its thread for the largest part of the gap; else to
    "no span".

A trace of a program without these spans gives empty `spans`, zero
`engine_host_s`, and every gap under the `bench.*` calls or "no span"; the
metric functions below then return None.
"""
from __future__ import annotations

import warnings
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench.trace import _union

NO_SPAN = "no span"

# what repro.obs opens on the served path (docs/observability.md)
PROGRAM_SPANS = frozenset((
    "admission.submit", "engine.compile", "engine.key",
    "admission.inline_flush", "admission.flush", "admission.resolve",
    "admission.fit", "engine.run_compiled", "engine.exact", "engine.plan",
    "engine.kernel", "engine.ci", "engine.fetch", "synopsis.fit",
    "synopsis.eval", "store.insert",
))


class _Event:
    __slots__ = ("name", "a", "b", "stats", "child_s", "waited_s")

    def __init__(self, name: str, a: float, b: float, stats: dict):
        self.name, self.a, self.b = name, a, b
        self.stats = stats
        self.child_s = 0.0      # covered by children on the same line
        self.waited_s = 0.0     # engine.run_compiled: fetches and fits in it


def _tracked(name: str) -> bool:
    return name in PROGRAM_SPANS or (name.startswith("bench.")
                                     and name != "bench.window")


def _read(pd) -> Tuple[Optional[Tuple[float, float]], List[List[_Event]],
                       List[Tuple[float, float]]]:
    """(window, tracked events per host line, device busy intervals)."""
    window = None
    lines: List[List[_Event]] = []
    busy: List[Tuple[float, float]] = []
    # jaxlib builds its stats type on first use, with a DeprecationWarning
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in pd.planes:
            if plane.name == "/host:CPU":
                for line in plane.lines:
                    evs = []
                    for ev in line.events:
                        if ev.name == "bench.window":
                            window = (ev.start_ns,
                                      ev.start_ns + ev.duration_ns)
                        elif _tracked(ev.name):
                            stats = (dict(ev.stats)
                                     if ev.name in PROGRAM_SPANS else {})
                            evs.append(_Event(ev.name, ev.start_ns,
                                              ev.start_ns + ev.duration_ns,
                                              stats))
                    if evs:
                        lines.append(evs)
            elif plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name == "XLA Modules":
                        busy.extend((ev.start_ns, ev.start_ns + ev.duration_ns)
                                    for ev in line.events)
    return window, lines, busy


def _nest(evs: List[_Event], w0: float, w1: float):
    """Walk one thread line's events in nesting order: fills each event's
    `child_s` and `waited_s`, and returns the segments (start, end, name of
    the innermost span) clipped to the window, in time order."""
    evs.sort(key=lambda e: (e.a, -e.b))
    segments: List[Tuple[float, float, str]] = []
    stack: List[_Event] = []
    cursor = w0

    def emit(upto: float, name: str) -> None:
        a, b = max(cursor, w0), min(upto, w1)
        if b > a:
            segments.append((a, b, name))

    for ev in evs:
        while stack and stack[-1].b <= ev.a:
            done = stack.pop()
            emit(done.b, done.name)
            cursor = max(cursor, done.b)
        if stack:
            emit(ev.a, stack[-1].name)
            stack[-1].child_s += (ev.b - ev.a) * 1e-9
            if ev.name in ("engine.fetch", "synopsis.fit"):
                for anc in stack:
                    if anc.name == "engine.run_compiled":
                        anc.waited_s += (ev.b - ev.a) * 1e-9
                        break
        cursor = max(cursor, ev.a)
        stack.append(ev)
    while stack:
        done = stack.pop()
        emit(done.b, done.name)
        cursor = max(cursor, done.b)
    return segments


def idle_gaps(busy: List[Tuple[float, float]], w0: float,
              w1: float) -> List[Tuple[float, float]]:
    """Intervals of the window in which no device program runs."""
    clipped = [(max(a, w0), min(b, w1)) for a, b in busy]
    gaps = []
    t = w0
    for a, b in _union([(a, b) for a, b in clipped if b > a]) + [(w1, w1)]:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    return gaps


def charge_gaps(gaps: List[Tuple[float, float]],
                lines: List[List[Tuple[float, float, str]]]) -> Dict[str, float]:
    """Seconds of each gap, under the name whose segments (on any one
    line) cover most of it; "no span" where none does.  `gaps` and each
    line's segments are sorted and disjoint."""
    cover: List[Dict[str, float]] = [defaultdict(float) for _ in gaps]
    for segs in lines:
        j = 0
        for g, (a, b) in enumerate(gaps):
            while j < len(segs) and segs[j][1] <= a:
                j += 1
            k = j
            while k < len(segs) and segs[k][0] < b:
                s0, s1, name = segs[k]
                cover[g][name] += min(b, s1) - max(a, s0)
                k += 1
    out: Dict[str, float] = defaultdict(float)
    for (a, b), c in zip(gaps, cover):
        name = max(c, key=c.get) if c else NO_SPAN
        out[name] += (b - a) * 1e-9
    return dict(out)


def reduce_spans(path: str) -> dict:
    """`spans`, `engine_host_s` and `idle_by_span` of the traced window of
    one trace file (see the module's docstring)."""
    from jax.profiler import ProfileData

    window, lines, busy = _read(ProfileData.from_file(path))
    if window is None:
        raise RuntimeError("no bench.window annotation in the trace")
    w0, w1 = window
    spans: Dict[str, dict] = {}
    engine_host_s = 0.0
    segments = []
    for evs in lines:
        segments.append(_nest(evs, w0, w1))
        for ev in evs:
            if ev.name not in PROGRAM_SPANS or not w0 <= ev.a < w1:
                continue
            dur = (ev.b - ev.a) * 1e-9
            s = spans.setdefault(ev.name, {"count": 0, "total_s": 0.0,
                                           "self_s": 0.0, "stats": {}})
            s["count"] += 1
            s["total_s"] += dur
            s["self_s"] += dur - ev.child_s
            for k, v in ev.stats.items():
                s["stats"][k] = s["stats"].get(k, 0) + v
            if ev.name == "engine.run_compiled":
                engine_host_s += dur - ev.waited_s
    by_span = charge_gaps(idle_gaps(busy, w0, w1), segments)
    return {
        "spans": spans,
        "engine_host_s": engine_host_s,
        "idle_by_span": sorted(([k, v] for k, v in by_span.items()),
                               key=lambda kv: -kv[1]),
    }


# --- the per-layer numbers these spans give (None without program spans) --

def _span(summary: dict, name: str) -> Optional[dict]:
    s = summary["spans"].get(name)
    return s if s and s["count"] > 0 else None


def queue_wait_ms(summary: dict) -> Optional[float]:
    """Mean time a query waits in the admission queue for its flush."""
    s = _span(summary, "admission.flush")
    if s is None or not s["stats"].get("batch"):
        return None
    return s["stats"]["wait_us"] / s["stats"]["batch"] / 1e3


def submit_us_per_query(summary: dict) -> Optional[float]:
    """Host time of `AqpSession.submit` per query: compile, keying and the
    session lock, without the flushes it runs inline."""
    s = _span(summary, "admission.submit")
    if s is None:
        return None
    inline = summary["spans"].get("admission.inline_flush", {})
    return (s["total_s"] - inline.get("total_s", 0.0)) * 1e6 / s["count"]


def plan_host_us_per_flush(summary: dict) -> Optional[float]:
    """Host time of the engine per flush, its waits for the device and its
    synopsis fits left out."""
    s = _span(summary, "engine.run_compiled")
    if s is None:
        return None
    return summary["engine_host_s"] * 1e6 / s["count"]


def fit_host_ms_per_refresh(summary: dict, refreshes: int) -> Optional[float]:
    """Host time of the synopsis fits per insert of the window."""
    s = _span(summary, "synopsis.fit")
    if s is None or refreshes <= 0:
        return None
    return s["total_s"] * 1e3 / refreshes
