"""repro.obs profiler mode: spans land in a `jax.profiler` trace as host
events with their numeric attributes as stats, nothing fences, kernels are
called directly, and with every mode off `obs.span` stays the shared no-op."""
import glob
import warnings

import numpy as np
import pytest

from repro import obs
from repro.core import AqpQuery, Eq, Range
from repro.data import TelemetryStore
from repro.obs import Tracer

# spans one traced window of queries plus an insert must show
CATALOGUE = (
    "admission.submit", "engine.compile", "engine.key",
    "admission.inline_flush", "admission.flush", "engine.run_compiled",
    "engine.exact", "engine.plan", "engine.kernel", "engine.ci",
    "engine.fetch", "admission.resolve", "synopsis.fit", "store.insert",
)


@pytest.fixture
def profiler_mode():
    obs.trace_on_profiler(True)
    yield
    obs.trace_on_profiler(False)


def _store(rng, n=4_000, capacity=256):
    store = TelemetryStore(capacity=capacity, seed=0)
    store.track_categorical("m")
    a = rng.normal(0, 1, n).astype(np.float32)
    m = rng.integers(0, 3, n).astype(np.float32)
    store.add_batch({"a": a, "m": m})
    return store


def _queries():
    return [AqpQuery("count", (Range("a", -1.0, 1.0),)),
            AqpQuery("sum", (Range("a", -0.5, 2.0),), target="a"),
            AqpQuery("count", (Eq("m", 1.0),))]


def _host_events(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    events = {}
    # jaxlib builds its stats type on first use, with a DeprecationWarning
    # that this suite raises as an error
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if plane.name != "/host:CPU":
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in CATALOGUE:
                        events.setdefault(ev.name, []).append(dict(ev.stats))
    return events


def test_profiler_trace_holds_every_span_with_stats(profiler_mode, rng,
                                                    tmp_path):
    import jax

    store = _store(rng)
    store.engine().execute(_queries())          # fit and compile untraced
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with store.session(watermark=None, max_delay=0.0,
                           auto_flush=False) as sess:
            futs = [sess.submit(q) for q in _queries()]
            store.add_batch({"a": rng.normal(0, 1, 64).astype(np.float32),
                             "m": np.ones(64, np.float32)})
            futs.append(sess.submit(_queries()[0]))   # refits after insert
            for f in futs:
                f.result(timeout=30)
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    missing = [name for name in CATALOGUE if name not in events]
    assert not missing, missing
    for stats in events["admission.flush"]:
        assert stats["batch"] >= 1 and stats["wait_us"] >= 0.0
    assert all(s["parts"] == 1 for s in events["admission.submit"])
    assert {s["rows"] for s in events["store.insert"]} == {64}
    fit = events["synopsis.fit"][0]
    assert fit["n"] == 256 and fit["d"] == 1
    assert "selector" not in fit            # strings stay out of the trace


def test_profiler_mode_never_fences_or_profiles(rng, monkeypatch):
    """Even with `enable()` on too: no block_until_ready, no profiled_call,
    no gated histogram, no extra jit trace, and bit-identical answers."""
    from jax._src.array import ArrayImpl

    from repro.core.aqp import batch_query_1d
    from repro.kernels import ops

    store = _store(rng)
    engine = store.engine(backend="pallas")
    want = engine.execute(_queries())
    traces = batch_query_1d._cache_size()

    fences = []
    real_bur = ArrayImpl.block_until_ready
    monkeypatch.setattr(ArrayImpl, "block_until_ready",
                        lambda self: fences.append(1) or real_bur(self))

    def no_profiling(*_a, **_k):
        raise AssertionError("profiled_call in profiler mode")

    monkeypatch.setattr(ops, "profiled_call", no_profiling)
    prev_tracer = obs.set_tracer(Tracer())
    was = obs.enabled()
    obs.enable()
    obs.trace_on_profiler(True)
    try:
        assert not obs.enabled()
        with engine.session(watermark=None, max_delay=0.0,
                            auto_flush=False) as sess:
            got = [f.result(timeout=30)
                   for f in [sess.submit(q) for q in _queries()]]
        direct = engine.execute(_queries())
    finally:
        obs.trace_on_profiler(False)
        if not was:
            obs.disable()
        ring = obs.set_tracer(prev_tracer).spans()
    assert fences == []
    assert ring == []                       # the ring records nothing
    assert batch_query_1d._cache_size() == traces
    assert store.metrics.sum_histogram("aqp.query.latency_us")[1] == 0
    assert any(r.path == "range1d:pallas" for r in got)
    for w, g, d in zip(want, got, direct):
        assert w.estimate == g.estimate == d.estimate
        assert w.ci_lo == g.ci_lo == d.ci_lo and w.ci_hi == g.ci_hi
        assert w.path == g.path == d.path


def test_every_mode_off_span_is_shared_noop(monkeypatch):
    obs.trace_on_profiler(True)
    assert isinstance(obs.span("x", n=1), obs.ProfilerSpan)
    obs.trace_on_profiler(False)
    was = obs.enabled()
    obs.disable()

    def no_annotation(*_a, **_k):
        raise AssertionError("an annotation was built with every mode off")

    monkeypatch.setattr(obs, "_annotation", no_annotation)
    try:
        s = obs.span("admission.submit", root=True, parts=1)
        assert s is obs.NOOP_SPAN and s.ctx is None
        with s as inner:
            assert inner.set(parts=2) is s
    finally:
        if was:
            obs.enable()


def test_submit_from_a_done_callback_starts_its_own_trace(rng):
    """admission.submit is a root even when a done-callback submits from
    inside another query's flush (inline here, under admission.submit)."""
    prev_tracer = obs.set_tracer(Tracer())
    was = obs.enabled()
    obs.enable()
    try:
        store = _store(rng)
        inner = []
        with store.session(watermark=2, max_delay=None,
                           auto_flush=False) as sess:
            first = sess.submit(_queries()[0])
            first.add_done_callback(
                lambda _f: inner.append(sess.submit(_queries()[2])))
            assert not first.done()
            sess.submit(_queries()[1])  # same bucket: the watermark flushes
            assert first.done() and len(inner) == 1
        inner[0].result(timeout=30)
        spans = obs.get_tracer().spans()
    finally:
        if not was:
            obs.disable()
        obs.set_tracer(prev_tracer)
    submits = [s for s in spans if s.name == "admission.submit"]
    assert len(submits) == 3
    assert all(s.parent_id is None for s in submits)
    assert len({s.trace_id for s in submits}) == 3
