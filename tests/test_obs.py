"""repro.obs: metrics registry thread-safety, span tracing and the
admit->flush->plan->kernel->ci tree, kernel profiling, checkpoint round-trip
of metrics state, and the zero-overhead-when-disabled contract."""
import json
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import AqpQuery, Range
from repro.data import TelemetryStore
from repro.obs import MetricsRegistry, Tracer


@pytest.fixture
def enabled():
    """Enable obs for one test with a fresh tracer; restore prior state."""
    prev_tracer = obs.set_tracer(Tracer())
    was = obs.enabled()
    obs.enable()
    yield obs.get_tracer()
    if not was:
        obs.disable()
    obs.set_tracer(prev_tracer)


def _store(rng, n=20_000, capacity=512):
    store = TelemetryStore(capacity=capacity, seed=0)
    a = rng.normal(0, 1, n).astype(np.float32)
    b = (0.8 * a + 0.6 * rng.normal(0, 1, n)).astype(np.float32)
    store.add_batch({"a": a, "b": b})
    return store


# --- registry: correctness under concurrency ---------------------------------

def test_counters_concurrent_increments_no_loss():
    reg = MetricsRegistry()
    n_threads, per = 8, 10_000
    barrier = threading.Barrier(n_threads)

    def work(ti):
        barrier.wait()
        for i in range(per):
            # re-resolve through the registry each time: the lookup path is
            # part of what must be thread-safe, not just Counter.inc
            reg.counter("t.hits", thread="shared").inc()
            reg.histogram("t.lat", thread="shared").observe(float(i % 100))
            reg.gauge("t.peak", thread="shared").max(float(i))

    threads = [threading.Thread(target=work, args=(ti,))
               for ti in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("t.hits", thread="shared").value == n_threads * per
    h = reg.histogram("t.lat", thread="shared")
    assert h.count == n_threads * per
    assert h.summary()["max"] == 99.0
    assert reg.gauge("t.peak", thread="shared").value == per - 1


def test_histogram_summary_and_percentiles():
    h = MetricsRegistry().histogram("h")
    for v in (10.0, 20.0, 30.0, 40.0, 1000.0):
        h.observe(v)
    s = h.summary()
    assert s["count"] == 5 and s["sum"] == 1100.0
    assert s["min"] == 10.0 and s["max"] == 1000.0
    # bucketed percentiles land within the enclosing 1-2-5 decade bucket
    assert 10.0 <= s["p50"] <= 50.0
    assert s["p99"] <= 1000.0          # clamped to the observed max


def test_registry_state_roundtrip_exact():
    reg = MetricsRegistry()
    reg.counter("c", k="x").inc(7)
    reg.gauge("g").set(3.5)
    for v in (5.0, 50.0, 500.0):
        reg.histogram("h", path="range1d").observe(v)
    fresh = MetricsRegistry()
    fresh.load_state(reg.state())
    assert fresh.counter("c", k="x").value == 7
    assert fresh.gauge("g").value == 3.5
    assert fresh.histogram("h", path="range1d").summary() == \
        reg.histogram("h", path="range1d").summary()


# --- tracing ------------------------------------------------------------------

def test_span_nesting_and_tree_with_fake_clock():
    clock = [0.0]
    tr = Tracer(clock=lambda: clock[0])
    with tr.span("root", job="q1") as root:
        clock[0] = 1.0
        with tr.span("child_a"):
            clock[0] = 2.0
        with tr.span("child_b"):
            clock[0] = 5.0
    tree = tr.tree(root.trace_id)
    assert len(tree) == 1 and tree[0]["name"] == "root"
    kids = tree[0]["children"]
    assert [k["name"] for k in kids] == ["child_a", "child_b"]
    assert kids[0]["duration_us"] == pytest.approx(1e6)
    assert kids[1]["duration_us"] == pytest.approx(3e6)
    assert tree[0]["duration_us"] == pytest.approx(5e6)
    assert tree[0]["attrs"] == {"job": "q1"}


def test_explicit_parent_links_across_threads():
    tr = Tracer()
    with tr.span("submit") as sub:
        ctx = sub.ctx
    out = {}

    def other_thread():
        with tr.span("flush", parent=ctx) as f:
            out["trace"], out["parent"] = f.trace_id, f.parent_id

    t = threading.Thread(target=other_thread)
    t.start()
    t.join()
    assert out["trace"] == sub.trace_id and out["parent"] == sub.span_id


def test_disabled_span_is_shared_noop():
    was = obs.enabled()
    obs.disable()
    try:
        s = obs.span("anything", attr=1)
        assert s is obs.NOOP_SPAN and s.ctx is None
        with s as inner:
            assert inner is s
    finally:
        if was:
            obs.enable()


def test_span_tree_reconstructs_admission_to_kernel_path(enabled, rng):
    """One traced query yields the full tree: admission.submit ->
    {engine.compile, engine.key, admission.flush -> engine.run_compiled ->
    {engine.exact, engine.plan, engine.kernel, engine.ci, engine.fetch}}, with
    the path recorded on the kernel span."""
    tracer = enabled
    store = _store(rng)
    engine = store.engine()
    engine.execute([AqpQuery("count", (Range("a", -1.0, 1.0),))])  # warm
    tracer.clear()
    with store.session(watermark=None, max_delay=None,
                       auto_flush=False) as sess:
        fut = sess.submit(AqpQuery("count", (Range("a", -0.5, 0.5),)))
        sess.flush()
        fut.result(timeout=10)
    submit = [s for s in tracer.spans() if s.name == "admission.submit"]
    assert len(submit) == 1
    tree = tracer.tree(submit[0].trace_id)
    assert [n["name"] for n in tree] == ["admission.submit"]
    assert tree[0]["attrs"]["parts"] == "1"
    children = tree[0]["children"]
    assert [n["name"] for n in children] == [
        "engine.compile", "engine.key", "admission.flush"]
    flush = children[2:]
    assert flush[0]["attrs"]["reason"] == "manual"
    assert flush[0]["attrs"]["batch"] == "1"
    assert float(flush[0]["attrs"]["wait_us"]) >= 0.0
    run = flush[0]["children"]
    assert [n["name"] for n in run] == ["engine.run_compiled",
                                        "admission.resolve"]
    names = [n["name"] for n in run[0]["children"]]
    assert names == ["engine.exact", "engine.plan", "engine.kernel",
                     "engine.ci", "engine.fetch"]
    kernel = next(n for n in run[0]["children"]
                  if n["name"] == "engine.kernel")
    assert kernel["attrs"]["path"] == "range1d"
    assert kernel["duration_us"] >= 0.0


# --- kernel profiling ---------------------------------------------------------

def test_kernel_profiling_records_fenced_timings(enabled, rng):
    from repro.kernels import ops, tuning

    x = rng.normal(0, 1, 256).astype(np.float32)
    pts = np.linspace(-1, 1, 32, dtype=np.float32)
    before = obs.get_registry().sum_counter("kernel.calls",
                                            kernel="kde_eval")
    ops.kde_eval(pts, x, np.float32(0.3))
    reg = obs.get_registry()
    assert reg.sum_counter("kernel.calls", kernel="kde_eval") == before + 1
    rows = tuning.measured("kde_eval")
    assert rows and rows[0]["kernel"] == "kde_eval"
    assert rows[0]["count"] >= 1 and rows[0]["max"] > 0.0


# --- durability: metrics ride the PR-5 checkpoint ----------------------------

def test_metrics_state_survives_checkpoint_roundtrip(rng, tmp_path):
    store = _store(rng)
    store.query([AqpQuery("count", (Range("a", -1.0, 1.0),))])
    ingested = store.metrics.sum_counter("aqp.ingest.rows", column="a")
    misses = store.metrics.sum_counter("aqp.cache.misses")
    assert ingested == 20_000 and misses >= 1
    store.save(tmp_path)
    loaded = TelemetryStore.load(tmp_path)
    assert loaded.metrics.sum_counter("aqp.ingest.rows",
                                      column="a") == ingested
    assert loaded.metrics.sum_counter("aqp.cache.misses") == misses
    # restored counters keep counting (no frozen snapshot semantics)
    loaded.add_batch({"a": rng.normal(0, 1, 100).astype(np.float32)})
    assert loaded.metrics.sum_counter("aqp.ingest.rows",
                                      column="a") == ingested + 100


# --- the zero-overhead-when-disabled contract --------------------------------

def test_disabled_mode_no_extra_jit_traces_and_bit_identity(rng):
    from repro.core.aqp import batch_query_1d

    assert not obs.enabled()
    store = _store(rng)
    engine = store.engine()
    specs = [AqpQuery("count", (Range("a", -1.0, 1.0),)),
             AqpQuery("avg", (Range("b", -0.5, 1.5),), target="b")]
    want = engine.execute(specs)
    traces = batch_query_1d._cache_size()
    # steady state: repeating the workload disabled adds no traces
    again = engine.execute(specs)
    assert batch_query_1d._cache_size() == traces
    # enabling obs must not re-trace either (same jitted callables), and
    # estimates stay bit-identical — instrumentation reads, never perturbs
    prev_tracer = obs.set_tracer(Tracer())
    obs.enable()
    try:
        instrumented = engine.execute(specs)
    finally:
        obs.disable()
        obs.set_tracer(prev_tracer)
    assert batch_query_1d._cache_size() == traces
    for w, a, i in zip(want, again, instrumented):
        assert w.estimate == a.estimate == i.estimate
        assert w.ci_lo == a.ci_lo == i.ci_lo
        assert w.path == a.path == i.path


def test_disabled_overhead_is_noise_level():
    """Micro-benchmark: a disabled span + fence is one predicate check and
    a shared no-op object — sub-microsecond territory.  The bound is set an
    order of magnitude above that so scheduler noise can't flake it."""
    import time

    assert not obs.enabled()
    n = 100_000
    t0 = time.perf_counter()
    for _ in range(n):
        with obs.span("noop", attr=1):
            pass
        obs.fence(None)
    per_ns = (time.perf_counter() - t0) / n * 1e9
    assert per_ns < 10_000, f"disabled span+fence costs {per_ns:.0f} ns/op"


def test_disabled_admission_counters_still_live(rng):
    """Counters/gauges are always-on (they back stats()); only spans,
    latency histograms, and fencing gate on enabled()."""
    assert not obs.enabled()
    store = _store(rng)
    with store.session(watermark=None, max_delay=None,
                       auto_flush=False) as sess:
        sess.submit(AqpQuery("count", (Range("a", -1.0, 1.0),)))
        sess.flush()
        st = sess.stats()
        assert st["submitted"] == 1 and st["flushes"] == 1
    # but the gated latency histogram stayed empty
    assert store.metrics.sum_histogram("aqp.query.latency_us")[1] == 0
    assert store.metrics.sum_histogram("aqp.query.latency_us",
                                       path="range1d")[1] == 0


# --- export -------------------------------------------------------------------

def test_export_json_merges_registries(tmp_path):
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.counter("a.hits", kind="store").inc(3)
    r2.histogram("k.wall", kernel="kde").observe(12.0)
    path = tmp_path / "m.json"
    doc = obs.export_json(str(path), r1, r2, extra={"mode": "test"})
    on_disk = json.loads(path.read_text())
    assert on_disk == json.loads(json.dumps(doc))
    assert on_disk["mode"] == "test" and "ts" in on_disk
    assert on_disk["counters"]["a.hits"] == [
        {"labels": {"kind": "store"}, "value": 3}]
    (entry,) = on_disk["histograms"]["k.wall"]
    assert entry["labels"] == {"kernel": "kde"} and entry["count"] == 1
