"""The traffic generator and the data generators: reproducible from the
seed, the same sizes for every seed, TPC-H bounds on half-steps."""
import json
import os

import numpy as np
import pytest

from bench import harness
from bench import traffic as traffic_mod
from bench.datagen import telemetry, tpch_lineitem

TRAFFIC = sorted(os.path.splitext(f)[0] for f in
                 os.listdir(os.path.join(harness.HERE, "traffic")))
STATS = {"loss": (0.0, 20.0), "latency_ms": (0.0, 300.0),
         "seq_len": (16.0, 2047.0)}


@pytest.mark.parametrize("name", TRAFFIC)
def test_schedule_reproducible_and_same_size(name):
    t = traffic_mod.load(name)
    assert t["loop"] == "open"
    a = traffic_mod.open_schedule(t, 3.0, np.random.default_rng(5), STATS)
    b = traffic_mod.open_schedule(t, 3.0, np.random.default_rng(5), STATS)
    c = traffic_mod.open_schedule(t, 3.0, np.random.default_rng(2**40 + 7),
                                  STATS)
    np.testing.assert_array_equal(a[0], b[0])
    assert a[1] == b[1]
    assert len(a[1]) == len(c[1])
    assert np.all(np.diff(a[0]) >= 0) and a[0][-1] < 3.0
    # every seed sends the same number of queries of each shape
    shape = lambda s: (str(s["group_by"]), s["preds"][0][0],
                       str(s["preds"][0][1]))
    assert sorted(map(shape, a[1])) == sorted(map(shape, c[1]))


def test_every_template_builds_a_query():
    for name in TRAFFIC:
        t = traffic_mod.load(name)
        rng = np.random.default_rng(0)
        for tmpl in t["block"] + ([t["refresh"]["probe"]]
                                  if t.get("refresh") else []):
            for _ in range(5):
                traffic_mod.to_query(traffic_mod.make_spec(tmpl, rng, STATS))


def _lattice(col):
    return {"l_quantity": (1.0, 1.0), "l_discount": (0.0, 0.01),
            "l_shipdate": (0.0, 1.0)}[col]


def test_tpch_bounds_sit_on_half_steps():
    t = traffic_mod.load("tpch-mix")
    rng = np.random.default_rng(11)
    for tmpl in t["block"]:
        for _ in range(50):
            for p in traffic_mod.make_spec(tmpl, rng, STATS)["preds"]:
                if p[0] != "range" or abs(p[2]) >= 1e9:
                    continue
                origin, step = _lattice(p[1])
                for v in p[2:]:
                    k = (v - origin) / step - 0.5
                    assert abs(k - round(k)) < 1e-6, (p, v)


def test_tpch_generator_fixed_rows_and_distributions():
    a = tpch_lineitem.generate(np.random.default_rng(3), 100_000)
    b = tpch_lineitem.generate(np.random.default_rng(3), 100_000)
    c = tpch_lineitem.generate(np.random.default_rng(4), 100_000)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
        assert a[k].shape == c[k].shape == (100_000,)
        assert a[k].dtype == np.float32
    assert set(np.unique(a["l_quantity"])) == set(np.arange(1, 51.0))
    assert set(np.round(np.unique(a["l_discount"]) * 100)) == set(range(11))
    late = a["l_shipdate"] > tpch_lineitem.CURRENTDATE
    np.testing.assert_array_equal(a["l_linestatus"][late], 1.0)
    ret = a["l_receiptdate"] <= tpch_lineitem.CURRENTDATE
    assert set(np.unique(a["l_returnflag"][ret])) == {0.0, 2.0}
    np.testing.assert_array_equal(a["l_returnflag"][~ret], 1.0)
    assert tpch_lineitem.CURRENTDATE == 1263 and tpch_lineitem.ENDDATE == 2556


def test_telemetry_generator_matches_serve():
    from repro.launch.serve import _make_telemetry

    a = telemetry.generate(np.random.default_rng(9), 5000)
    b = _make_telemetry(np.random.default_rng(9), 5000)
    for k in b:
        np.testing.assert_array_equal(a[k], b[k])


def test_configs_name_their_files():
    bench = harness.load_benchmark()
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(harness.ROOT, c["file"])))
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
