"""The reduction of the program's own spans (bench/spans.py): self time,
the engine's host time and idle-gap attribution on hand-built intervals,
then everything on a short telemetry.dash window recorded on one TPU v5e
with `repro.obs` in profiler mode."""
import os

import pytest

from bench import spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
DASH = os.path.join(DATA, "dash-spans.xplane.pb")
WINDOW = os.path.join(DATA, "window.xplane.pb")


def _events(*rows):
    return [spans._Event(name, a, b, {}) for name, a, b in rows]


def test_self_time_and_innermost_segments():
    evs = _events(("bench.submit", 0, 100), ("admission.submit", 10, 90),
                  ("engine.compile", 20, 30), ("engine.key", 30, 40),
                  ("admission.inline_flush", 50, 80))
    segs = spans._nest(evs, 0, 1000)
    child = {e.name: e.child_s * 1e9 for e in evs}
    assert child["bench.submit"] == pytest.approx(80)
    assert child["admission.submit"] == pytest.approx(50)
    assert child["engine.compile"] == 0
    assert segs == [(0, 10, "bench.submit"), (10, 20, "admission.submit"),
                    (20, 30, "engine.compile"), (30, 40, "engine.key"),
                    (40, 50, "admission.submit"),
                    (50, 80, "admission.inline_flush"),
                    (80, 90, "admission.submit"), (90, 100, "bench.submit")]


def test_segments_clip_to_the_window_and_skip_unspanned_time():
    evs = _events(("engine.plan", 0, 30), ("engine.kernel", 50, 70))
    assert spans._nest(evs, 10, 60) == [(10, 30, "engine.plan"),
                                        (50, 60, "engine.kernel")]


def test_engine_waits_and_fits_leave_the_host_time():
    evs = _events(("engine.run_compiled", 0, 100), ("engine.plan", 5, 40),
                  ("synopsis.fit", 10, 30), ("engine.kernel", 40, 50),
                  ("engine.fetch", 50, 90))
    spans._nest(evs, 0, 1000)
    run = evs[0]
    assert run.waited_s * 1e9 == pytest.approx(20 + 40)


def test_gaps_go_to_the_innermost_span_covering_most():
    gaps = spans.idle_gaps([(0, 10), (40, 50), (45, 60), (90, 100)], 0, 100)
    assert gaps == [(10, 40), (60, 90)]
    submitter = [(5, 25, "admission.submit"), (25, 30, "bench.submit")]
    flusher = [(12, 40, "engine.plan"), (60, 65, "engine.fetch")]
    by = spans.charge_gaps(gaps, [submitter, flusher])
    # gap 1: plan 28 ns against submit 15 and bench.submit 5; gap 2 has
    # only the fetch in it: the fetch
    assert by == {"engine.plan": pytest.approx(30e-9),
                  "engine.fetch": pytest.approx(30e-9)}
    assert spans.charge_gaps([(0, 5)], [submitter]) == {
        spans.NO_SPAN: pytest.approx(5e-9)}


def test_metrics_from_a_summary():
    summary = {"spans": {
        "admission.flush": {"count": 4, "total_s": 0.01, "self_s": 0.002,
                            "stats": {"batch": 10, "wait_us": 50_000.0}},
        "admission.submit": {"count": 10, "total_s": 0.006, "self_s": 0.001,
                             "stats": {"parts": 10}},
        "admission.inline_flush": {"count": 2, "total_s": 0.004,
                                   "self_s": 0.0, "stats": {}},
        "engine.run_compiled": {"count": 4, "total_s": 0.008,
                                "self_s": 0.001, "stats": {"n": 10}},
        "synopsis.fit": {"count": 9, "total_s": 0.018, "self_s": 0.018,
                         "stats": {"n": 9 * 256}},
    }, "engine_host_s": 0.006, "idle_by_span": []}
    assert spans.queue_wait_ms(summary) == pytest.approx(5.0)
    assert spans.submit_us_per_query(summary) == pytest.approx(200.0)
    assert spans.plan_host_us_per_flush(summary) == pytest.approx(1500.0)
    assert spans.fit_host_ms_per_refresh(summary, 2) == pytest.approx(9.0)
    assert spans.fit_host_ms_per_refresh(summary, 0) is None


def test_a_trace_without_program_spans_reads_none():
    """The benchmark's PR 12 fixture: no program spans, so no metric, and
    every idle gap stays under a `bench.*` call or "no span", as many
    seconds as `trace.reduce` charges there."""
    summary = spans.reduce_spans(WINDOW)
    assert summary["spans"] == {} and summary["engine_host_s"] == 0.0
    assert spans.queue_wait_ms(summary) is None
    assert spans.submit_us_per_query(summary) is None
    assert spans.plan_host_us_per_flush(summary) is None
    assert spans.fit_host_ms_per_refresh(summary, 1) is None
    base = trace.reduce(WINDOW)
    idle = dict(summary["idle_by_span"])
    assert set(idle) <= {"bench.submit", "bench.insert", spans.NO_SPAN}
    assert sum(idle.values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-6)


# --- a telemetry.dash window recorded on one TPU v5e, spans on --------------

@pytest.fixture(scope="module")
def dash():
    return spans.reduce_spans(DASH)


def test_dash_spans_nest_and_count(dash):
    s = dash["spans"]
    for name in ("admission.submit", "engine.compile", "engine.key",
                 "admission.inline_flush", "admission.flush",
                 "engine.run_compiled", "engine.exact", "engine.plan",
                 "engine.kernel", "engine.ci", "engine.fetch",
                 "admission.resolve"):
        assert s[name]["count"] > 0, name
        assert 0 <= s[name]["self_s"] <= s[name]["total_s"], name
    # one compile and one keying per submit; one engine pass per flush
    assert s["engine.compile"]["count"] == s["admission.submit"]["count"]
    assert s["engine.key"]["stats"]["parts"] == \
        s["admission.submit"]["stats"]["parts"]
    assert s["engine.run_compiled"]["count"] == s["admission.flush"]["count"]
    assert s["engine.run_compiled"]["stats"]["n"] == \
        s["admission.flush"]["stats"]["batch"]
    assert 0 < dash["engine_host_s"] < s["engine.run_compiled"]["total_s"]


def test_dash_metrics_read(dash):
    assert spans.queue_wait_ms(dash) >= 0
    assert spans.submit_us_per_query(dash) >= 0
    assert spans.plan_host_us_per_flush(dash) >= 0
    assert spans.fit_host_ms_per_refresh(dash, 1) is None   # no insert


def test_dash_idle_goes_to_program_spans(dash):
    base = trace.reduce(DASH)
    idle = dict(dash["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-6)
    # what trace.reduce puts under bench.submit is program work
    assert dict(base["idle_gaps"]).get("bench.submit", 0) > 0
    # the kernels' `name=` keeps the operation names the trace showed
    assert "jit_batch_query_1d/_aqp_batch_sums" in dict(base["device_ops"])
    bench = sum(v for k, v in idle.items() if k.startswith("bench."))
    assert bench <= 0.1 * sum(idle.values())
    assert set(idle) - {spans.NO_SPAN} <= spans.PROGRAM_SPANS | {
        "bench.submit"}
