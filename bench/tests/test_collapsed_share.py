"""`fit.collapsed_axis_share` reads the program's process-wide count of
one-axis LSCV_h fits, the share of them that ran over an axis's distinct
values: 0 where the counter is there and no LSCV_h axis was fitted, and
nothing from a program without that counter."""
import pytest

from bench import harness

import repro.data.aqp_store  # noqa: F401  (declares the counters)
from repro import obs


def _read():
    ctx = harness.LayerContext(None, [], 0, 1, {}, 32768, {})
    return harness.load_module("metrics", "fit.collapsed_axis_share").read(ctx)


@pytest.mark.parametrize("collapsed, full, want", [
    (2, 5, pytest.approx(200.0 / 7)),   # 2 tied of telemetry.fit-lscv's 7
    (0, 7, 0.0),                        # no tied axis
    (0, 0, 0.0),                        # no LSCV_h fit
])
def test_collapsed_axis_share(monkeypatch, collapsed, full, want):
    reg = obs.MetricsRegistry()
    reg.counter("aqp.synopsis.axis_fits")
    reg.counter("aqp.synopsis.axis_fits", selector="plugin",
                collapsed=0).inc(9)
    if collapsed:
        reg.counter("aqp.synopsis.axis_fits", selector="lscv_h",
                    collapsed=1).inc(collapsed)
    if full:
        reg.counter("aqp.synopsis.axis_fits", selector="lscv_h",
                    collapsed=0).inc(full)
    monkeypatch.setattr(obs, "get_registry", lambda: reg)
    assert _read() == want


def test_program_without_the_counter_reads_nothing(monkeypatch):
    monkeypatch.setattr(obs, "get_registry", lambda: obs.MetricsRegistry())
    assert _read() is None
