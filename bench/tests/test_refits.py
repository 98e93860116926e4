"""`fit.refits_per_refresh` reads the program's process-wide fit counter
(its fits of stale synopses), per refresh, and reads nothing from a program
without that counter."""
import pytest

from bench import harness

from repro import obs


def _read(refreshes):
    ctx = harness.LayerContext(None, [], 0, refreshes, {}, 32768, {})
    return harness.load_module("metrics", "fit.refits_per_refresh").read(ctx)


@pytest.mark.parametrize("counted, refreshes, want", [
    (14, 2, 7.0),           # the window's refits over its refreshes
    (0, 3, 0.0),            # refreshes that forced no refit
    (14, 0, None),          # no refresh in the cell
])
def test_refits_per_refresh(monkeypatch, counted, refreshes, want):
    reg = obs.MetricsRegistry()
    reg.counter("aqp.synopsis.fits", selector="lscv_h", stale=0).inc(9)
    reg.counter("aqp.synopsis.fits", selector="lscv_h", stale=1).inc(counted)
    monkeypatch.setattr(obs, "get_registry", lambda: reg)
    assert _read(refreshes) == want


def test_program_without_the_counter_reads_nothing(monkeypatch):
    monkeypatch.setattr(obs, "get_registry", lambda: obs.MetricsRegistry())
    assert _read(2) is None
