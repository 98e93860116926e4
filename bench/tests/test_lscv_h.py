"""The LSCV_h reference (`estimators/lscv_h.py`) and its cost count:

  * g(h) on the whole grid, sigma and the bandwidth against a float64 brute
    force at n = 512;
  * a run of `telemetry.fit-lscv` at a small size comes out correct, and not
    correct when the program serves the bare grid value as its bandwidth
    (the fault of a synopsis that forgets sigma);
  * the cost module against a hand count.
"""
import math
import time

import numpy as np
import pytest

from bench import harness
from bench.reference import Precision


def _g64(x: np.ndarray, grid: np.ndarray) -> np.ndarray:
    """eq. 24 (eq. 43 form) in float64 over every pair i < j."""
    x = x.astype(np.float64)
    n = x.size
    sigma = np.std(x, ddof=1)
    i, j = np.triu_indices(n, 1)
    s = ((x[i] - x[j]) / sigma) ** 2
    c_k = (2.0 * math.pi) ** -0.5 / sigma
    c_kk = (4.0 * math.pi) ** -0.5 / sigma
    t = np.asarray([np.sum(c_kk * np.exp(-s / (4 * h * h))
                           - 2.0 * c_k * np.exp(-s / (2 * h * h)))
                    for h in grid])
    return (2.0 / n ** 2 * t + c_kk / n) / grid


@pytest.mark.parametrize("n", [511, 512])
@pytest.mark.parametrize("column", ["loss", "latency_ms", "seq_len"])
def test_lscv_axis_against_float64_brute_force(n, column):
    gen = harness.load_module("datagen", "telemetry")
    x = gen.generate(np.random.default_rng(5), n)[column]
    est = harness.load_module("estimators", "lscv_h")
    got = est.lscv_axis(x)
    h0 = (4.0 / (3.0 * n)) ** 0.2
    np.testing.assert_allclose(got["grid"], np.linspace(h0 / 4, 4 * h0, 150),
                               rtol=1e-15)
    assert got["sigma"] == pytest.approx(np.std(x.astype(np.float64),
                                                ddof=1), rel=1e-12)
    g = _g64(x, got["grid"])
    np.testing.assert_allclose(got["g"], g, rtol=1e-6)
    assert np.argmin(got["g"]) == np.argmin(g)
    assert got["bw"] == got["grid"][np.argmin(g)] * got["sigma"]
    # the bfloat16 control computes the same g to about 3 digits only
    ctl = est.lscv_axis(x, Precision("bfloat16"))
    assert np.max(np.abs(ctl["g"] - g) / np.abs(g)) > 1e-4


def test_bandwidth_gap_reads_both_gaps():
    est = harness.load_module("estimators", "lscv_h")
    x = harness.load_module("datagen", "telemetry").generate(
        np.random.default_rng(6), 400)
    sample = np.stack([x["loss"], x["latency_ms"]], axis=1)
    ref = est.bandwidth(sample)
    same = {"bw": ref["bw"].copy(), "grid_h": ref["grid_h"].copy()}
    assert est.bandwidth_gap(same, ref) == 0.0
    # (a) one grid point off on the second axis: g's excess there
    j = int(np.argmin(ref["g"][1]))
    k = j + 1 if j + 1 < 150 else j - 1
    moved = {"bw": ref["bw"].copy(), "grid_h": ref["grid_h"].copy()}
    moved["grid_h"][1] = ref["grid"][1][k]
    moved["bw"][1] = ref["grid"][1][k] * ref["sigma"][1]
    want = (ref["g"][1][k] - ref["g"][1][j]) / abs(ref["g"][1][j])
    assert est.bandwidth_gap(moved, ref) == pytest.approx(want, rel=1e-12)
    # (b) the bare grid value served as the bandwidth (sigma forgotten)
    bare = {"bw": ref["grid_h"].copy(), "grid_h": ref["grid_h"].copy()}
    assert est.bandwidth_gap(bare, ref) > 0.1


def _bare_h(monkeypatch):
    """The program's LSCV_h synopses serve their grid value unscaled."""
    from repro.core import aqp

    real = aqp.KDESynopsis.fit.__func__

    def fit(cls, *args, **kwargs):
        syn = real(cls, *args, **kwargs)
        if syn.selector == "lscv_h":
            syn.h = syn.grid_h
        return syn
    monkeypatch.setattr(aqp.KDESynopsis, "fit", classmethod(fit))


def small_fit_lscv_run(seconds=3.0, seed=20241016):
    """`telemetry.fit-lscv` on the CPU at a small size: 512-row reservoirs,
    a 300-row insert every second."""
    from bench import traffic as traffic_mod

    refresh = dict(traffic_mod.load("dash-refresh")["refresh"], every_s=1.0,
                   rows=300)
    overrides = {"config": {"data": {"rows": 20000},
                            "store": {"capacity": 512}},
                 "traffic": {"rate_qps": 24.0, "refresh": refresh},
                 "warm_sizes": (8,)}
    return harness.run_cell("telemetry.fit-lscv", seed, seconds, False,
                            time.time(), overrides=overrides,
                            log=lambda msg: None)


@pytest.mark.parametrize("fault", ["sound", "bare_h"])
def test_fit_lscv_run_is_checked_by_its_selector(fault, monkeypatch):
    if fault == "bare_h":
        _bare_h(monkeypatch)
    result, extra = small_fit_lscv_run()
    assert extra["readings"]["compared"]["bandwidths"] > 0
    assert result["correct"] is (fault == "sound"), result["checks"]
    if fault == "bare_h":
        assert result["checks"]["h_gap"]["value"] > 0.1


def test_lscv_h_hand_count():
    cost = harness.load_module("cost", "lscv_h")
    # n = 4: 6 pairs x (3 + 8 x 150) + 3 x 4 + 10 x 150
    assert cost.call_flops(4) == 6 * 1203 + 12 + 1500
    assert cost.call_bytes(4) == 32
