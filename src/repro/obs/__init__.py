"""`repro.obs` — dependency-free observability for the AQP stack.

Two kinds of instrumentation with different cost profiles:

  * **Always-on**: counters and gauges (`MetricsRegistry`).  These back the
    public `stats()` dicts and cost one lock + one float add per event — no
    gating needed, and keeping them live is what makes the multi-session
    aggregation bug fixable (closed sessions' counters persist in the
    store registry instead of dying with the session weakref).

  * **Gated on `enabled()`**: span tracing into the ring, per-path latency
    histograms with `block_until_ready` fencing, and kernel profiling.
    Fencing changes dispatch behaviour (it synchronises the device), so
    these are opt-in: set ``REPRO_OBS=1`` in the environment or call
    :func:`enable` (e.g. ``repro.launch.serve --metrics-out ...`` does).
    When disabled, `span()` returns a shared no-op object and the kernel
    wrappers take the un-instrumented branch — zero extra jit traces and
    bit-identical numerics, both test-enforced.

  * **Profiler mode** (:func:`trace_on_profiler`): each span opens a
    `jax.profiler.TraceAnnotation` instead, so it lands in a
    `jax.profiler` trace on the device trace's clock.  This mode never
    fences: it takes precedence over `enable()`, `enabled()` reads False
    while it is on, and the gated histograms and kernel profiling stay
    off.  Device time comes from the device trace itself.

Scoping: each `TelemetryStore` owns a registry (`store.metrics`) so tests
and co-hosted stores stay isolated; kernel profiling and benchmarks write
to the process-global registry (`get_registry()`), since kernels have no
store handle.  `export_json` merges any number of registries into one
snapshot file.
"""
from __future__ import annotations

import json
import os
import tempfile
import time
from typing import Optional, Tuple

from repro import knobs

from .registry import (Counter, Gauge, Histogram, LATENCY_BUCKETS_US,
                       MetricsRegistry)
from .trace import NOOP_SPAN, ProfilerSpan, Span, Tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "LATENCY_BUCKETS_US", "MetricsRegistry",
    "NOOP_SPAN", "ProfilerSpan", "Span", "Tracer", "disable", "enable",
    "enabled", "fence", "export_json", "get_registry", "get_tracer",
    "set_tracer", "span", "trace_on_profiler",
]

_enabled = knobs.get_bool("REPRO_OBS")
_profiler = False
_annotation = None      # jax.profiler.TraceAnnotation, once profiler mode is on
_registry = MetricsRegistry()
_tracer = Tracer()


def enabled() -> bool:
    """True when the fenced instrumentation (ring tracing, fenced latency
    histograms, kernel profiling) is active: after `enable()`, and not in
    profiler mode."""
    return _enabled and not _profiler


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def trace_on_profiler(on: bool = True) -> None:
    """Switch profiler mode on or off.

    On, every `span()` opens a `jax.profiler.TraceAnnotation` named after
    the span for its lifetime; its int and float attributes, given at open
    or later through `set()`, become the event's stats (strings and None
    stay out).  The events are recorded only while a `jax.profiler` trace
    runs, on the thread that opened the span.  Nothing fences, `enabled()`
    reads False, and the ring records nothing.  `jax.profiler` is imported
    here, on first use, so this package still imports only the standard
    library."""
    global _profiler, _annotation
    if on and _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    _profiler = bool(on)


def get_registry() -> MetricsRegistry:
    """The process-global registry (kernel profiling, benchmarks)."""
    return _registry


def get_tracer() -> Tracer:
    return _tracer


def set_tracer(tracer: Tracer) -> Tracer:
    """Swap the global tracer (tests inject a fake-clock tracer); returns
    the previous one so callers can restore it."""
    global _tracer
    prev, _tracer = _tracer, tracer
    return prev


def span(name: str, parent: Optional[Tuple[int, int]] = None,
         root: bool = False, **attrs):
    """Open a span: a profiler annotation in profiler mode, else a span on
    the global tracer, or the shared no-op when both are off.

    The no-op singleton means a disabled `with obs.span(...):` costs one
    function call and no allocation; no annotation is built then either.
    `parent` links ring spans across threads and `root` starts a new trace
    (see `Tracer.span`); the profiler places its events by thread and time
    and takes neither."""
    if _profiler:
        return ProfilerSpan(_annotation, name, attrs)
    if not _enabled:
        return NOOP_SPAN
    return _tracer.span(name, parent=parent, root=root, **attrs)


def fence(*values) -> None:
    """Block until every jax array in `values` is device-ready, so the
    enclosing span measures real device time rather than dispatch time.
    Non-jax values pass through silently; no-op unless `enabled()` (so
    never in profiler mode)."""
    if not _enabled or _profiler:
        return
    for v in values:
        bur = getattr(v, "block_until_ready", None)
        if bur is not None:
            bur()


def export_json(path: str, *registries: MetricsRegistry,
                extra: Optional[dict] = None) -> dict:
    """Atomically write the merged snapshot of `registries` (default: the
    global one) as JSON; returns the written document.

    Snapshots merge at the metric-name level: later registries win on a
    (name, labels) clash, which cannot happen for the store/global split
    (disjoint metric names)."""
    regs = registries or (_registry,)
    doc = {"ts": time.time(), "counters": {}, "gauges": {}, "histograms": {}}
    for reg in regs:
        snap = reg.snapshot()
        for kind in ("counters", "gauges", "histograms"):
            for name, entries in snap[kind].items():
                doc[kind].setdefault(name, []).extend(entries)
    if extra:
        doc.update(extra)
    d = os.path.dirname(os.path.abspath(path)) or "."
    fd, tmp = tempfile.mkstemp(dir=d, suffix=".tmp")
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            json.dump(doc, f, sort_keys=True)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return doc
