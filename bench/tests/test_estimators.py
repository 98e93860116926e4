"""The check follows the configuration's `engine.selector` through one
estimator module per selector (`bench/estimators/<selector>.py`).

  * PLUGIN, moved into its module, gives exactly the readings and worst
    items the reference gave before the move (pinned in
    `data/golden_readings.json`, recorded from the tree before it);
  * a selector with no module stops a run before any data is made;
  * a second module, kept in `data/estimators/`, is picked by a
    configuration that names its selector, and a run checked by it comes
    out correct, and not correct with its answers altered or when PLUGIN's
    module checks it in its place.
"""
import json
import os

import numpy as np
import pytest

from bench import compare, harness
from bench import traffic as traffic_mod
from bench.tests._cpu_run import small_run
from bench.tests.test_faults import FAULTS

DATA = os.path.join(os.path.dirname(__file__), "data")
GOLDEN = os.path.join(DATA, "golden_readings.json")
TRAFFIC = {"telemetry-4m-32k": "dash", "tpch-lineitem-sf1": "tpch-mix"}


def build_work(name, rows=20000, capacity=512, per_template=2):
    """The `work` a run hands to `compare.readings`, made without a window:
    every template of the configuration's traffic twice, answered through
    the engine at synopsis versions 1, 2 and 3 (two 300-row inserts), the
    program's bandwidths at version 3.  Returns (work, synopses)."""
    cfg = harness.load_json(harness.ROOT, "bench", "configs", f"{name}.json")
    cfg["data"]["rows"] = rows
    cfg["store"]["capacity"] = capacity
    params = cfg["data"]["params"]
    gen = harness.load_module("datagen", cfg["data"]["generator"])
    data = gen.generate(np.random.default_rng(7), rows, params)
    batches = [gen.generate(np.random.default_rng(8 + k), 300, params)
               for k in range(2)]
    store = harness.build_store(cfg, data)
    engine = store.engine(backend="jnp", selector="plugin")
    traffic = traffic_mod.load(TRAFFIC[name])
    stats = {c: (float(v.min()), float(v.max())) for c, v in data.items()}
    rng = np.random.default_rng(3)
    joints = harness.joint_keys(cfg["store"])
    specs = [traffic_mod.make_spec(t, rng, stats)
             for t in traffic["block"] for _ in range(per_template)]
    plain = [s for s in specs if not s["group_by"]]
    kde, exact = [], []
    for step in range(3):
        answered = list(zip(plain, engine.execute(
            [traffic_mod.to_query(s) for s in plain])))
        for s in specs:
            if s["group_by"]:       # one answer per group value
                answered += [(s, r) for r in
                             engine.execute([traffic_mod.to_query(s)])]
        for s, r in answered:
            item = (s, r, harness.spec_key(s, joints),
                    int(r.synopsis_version))
            (exact if r.path.startswith("exact") else kde).append(item)
        if step < 2:
            store.add_batch(batches[step])
    synopses, h_prog = {}, {}
    for key in sorted({it[2] for it in kde}, key=str):
        res = store.joints[key] if isinstance(key, tuple) \
            else store.columns[key]
        syn = store.cache.peek(key, "plugin", res.version)
        synopses[key] = syn
        h_prog[(key, res.version)] = np.asarray(syn.h_diag(), np.float64)
    work = {"store": cfg["store"], "data": data, "batches": batches,
            "kde": kde, "exact": exact, "h_prog": h_prog,
            "unanswered": 0, "stale": 0}
    return work, synopses


def pinned(readings: dict) -> dict:
    """The part of `compare.readings` that is pinned, as JSON reads it."""
    return json.loads(json.dumps({"values": readings["values"],
                                  "worst": readings["worst"]}))


@pytest.mark.parametrize("name", sorted(TRAFFIC))
def test_plugin_module_gives_the_readings_of_before(name):
    work, synopses = build_work(name)
    plugin = harness.load_module("estimators", "plugin")
    for syn in synopses.values():
        np.testing.assert_array_equal(plugin.program_bandwidth(syn),
                                      np.asarray(syn.h_diag(), np.float64))
    with open(GOLDEN) as fh:
        golden = json.load(fh)[name]
    assert pinned(compare.readings(work, plugin)) == golden["program"]
    assert pinned(compare.readings(work, plugin, control="bfloat16")) \
        == golden["control"]


def test_selector_without_module_stops_before_data(monkeypatch):
    inner = harness.load_module

    def load_module(kind, name, root=harness.HERE):
        assert kind != "datagen", "data made before the selector was found"
        return inner(kind, name, root)
    monkeypatch.setattr(harness, "load_module", load_module)
    with pytest.raises(FileNotFoundError,
                       match=r"estimators/nonesuch\.py is missing"):
        small_run("tpch.refresh-quiesced",
                  config={"engine": {"selector": "nonesuch"}})


def _estimator_for(monkeypatch, selector, module, root):
    """A run that asks for `selector`'s estimator gets `module` from
    `<root>/estimators/`; returns the selectors asked for."""
    inner = harness.load_module
    asked = []

    def load_module(kind, name, root_=harness.HERE):
        if kind == "estimators":
            asked.append(name)
            if name == selector:
                return inner(kind, module, root)
        return inner(kind, name, root_)
    monkeypatch.setattr(harness, "load_module", load_module)
    return asked


@pytest.mark.parametrize("fault", ["sound", "answer_altered"])
def test_second_estimator_is_picked_by_its_selector(fault, monkeypatch):
    asked = _estimator_for(monkeypatch, "silverman", "silverman", DATA)
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    result, extra = small_run("tpch.refresh-quiesced",
                              config={"engine": {"selector": "silverman"}})
    assert asked[0] == "silverman"
    assert extra["readings"]["compared"]["bandwidths"] > 0
    assert result["correct"] is (fault == "sound"), result["checks"]


def test_second_estimator_is_not_plugin(monkeypatch):
    """The same run checked by PLUGIN's module in place of its own: the
    bandwidths differ, so the check must not pass."""
    _estimator_for(monkeypatch, "silverman", "plugin", harness.HERE)
    result, _extra = small_run("tpch.refresh-quiesced",
                               config={"engine": {"selector": "silverman"}})
    assert result["checks"]["h_gap"]["value"] > 0.01
    assert result["correct"] is False
