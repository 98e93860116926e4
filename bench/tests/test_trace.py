"""The reduction from a profiler trace to per-layer numbers, on a small
trace recorded on one TPU v5e: inside a `bench.window` annotation, three
flushes of 8 ranges and two of 8 boxes (each an estimate and a moments
program) under `bench.submit`, and one PLUGIN fit under `bench.insert`."""
import os

import pytest

from bench import trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "window.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace.reduce(FIXTURE)


def test_programs_counted_and_attributed(summary):
    calls = summary["program_calls"]
    assert calls["jit_batch_query_1d"] == 3
    assert calls["jit_moments_1d"] == 3
    assert calls["jit_batch_query_box"] == 2
    assert calls["jit_moments_box"] == 2
    assert calls["jit_plugin_bandwidth"] == 1
    assert summary["layer_calls"]["estimate"] == 10
    assert summary["layer_calls"]["fit"] == 1
    # every program of the window lands in exactly one layer
    assert sum(summary["layer_calls"].values()) == sum(calls.values())


def test_busy_and_layer_time(summary):
    assert summary["devices"] == 1
    assert 0 < summary["busy_s"] < summary["window_s"]
    layers = summary["layer_s"]
    assert layers["fit"] > layers["estimate"] > 0
    assert sum(layers.values()) == pytest.approx(summary["busy_s"], rel=1e-6)
    assert summary["window_s"] == pytest.approx(0.14705, abs=1e-4)


def test_breakdown(summary):
    ops = dict(summary["device_ops"])
    assert len(summary["device_ops"]) <= 10
    assert max(ops, key=ops.get).startswith("jit_plugin_bandwidth/")
    assert "jit_batch_query_1d/_aqp_batch_sums" in ops
    idle = dict(summary["idle_gaps"])
    assert set(idle) <= {"bench.submit", "bench.insert", trace.NO_CALL}
    assert idle["bench.submit"] > idle["bench.insert"] > 0
    assert sum(idle.values()) == pytest.approx(
        summary["window_s"] - summary["busy_s"], rel=1e-6)


def test_layer_files_are_well_formed():
    for name in trace.layer_names():
        [(got, patterns)] = trace.load_layers([name])
        assert got == name and patterns


def test_unknown_program_goes_to_other():
    layers = trace.load_layers(trace.layer_names())
    name = "jit_convert_element_type"
    assert not any(p.search(name) for _n, pats in layers for p in pats)


def test_metric_readers_on_the_fixture(summary):
    from bench import harness

    peaks = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    ctx = harness.LayerContext(summary, [("range1d", 1, 1)] * 24
                               + [("box", 2, 1)] * 16, 40, 1,
                               {"flushes": 5, "rows": 40}, 32768, peaks)
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        value = harness.load_module("metrics", m["name"]).read(ctx)
        assert value is not None and value >= 0, m["name"]
        if m["unit"] == "%":
            assert value <= 100.0, (m["name"], value)
