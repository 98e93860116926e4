"""The plain reference against the program at small sizes: the reservoir
replay reproduces the store's samples exactly, and the PLUGIN estimator's
bandwidth and KDE answers agree to float32 rounding."""
import numpy as np
import pytest

from bench import harness
from bench import reference as ref
from bench import traffic as traffic_mod

CONFIGS = ("telemetry-4m-32k", "tpch-lineitem-sf1")
PLUGIN = harness.load_module("estimators", "plugin")


def _small(name, rows=6000, capacity=256):
    cfg = harness.load_json(harness.ROOT, "bench", "configs", f"{name}.json")
    cfg["data"]["rows"] = rows
    cfg["store"]["capacity"] = capacity
    gen = harness.load_module("datagen", cfg["data"]["generator"])
    data = gen.generate(np.random.default_rng(7), rows, cfg["data"]["params"])
    return cfg, gen, data


@pytest.mark.parametrize("name", CONFIGS)
def test_reservoir_replay_matches_store(name):
    cfg, gen, data = _small(name)
    store = harness.build_store(cfg, data)
    batch = gen.generate(np.random.default_rng(8), 500, cfg["data"]["params"])
    store.add_batch(batch)
    keys = [k for k in store.columns] + list(store.joints)
    res = ref.build_reservoirs(cfg["store"], keys, data)
    ref.add_rows(res, batch)
    for k in keys:
        prog = store.joints[k] if isinstance(k, tuple) else store.columns[k]
        np.testing.assert_array_equal(res[k].sample(), prog.sample())
        assert res[k].n_seen == prog.n_seen
        assert res[k].version == prog.version == 2


@pytest.mark.parametrize("seed", [0, 1])
def test_plugin_matches_program(seed):
    from repro.core import plugin_bandwidth

    x = np.random.default_rng(seed).gamma(3.0, 0.7, 2048).astype(np.float32)
    h_prog = float(plugin_bandwidth(x).h)
    h_ref = PLUGIN.plugin_h(x)
    assert abs(h_prog - h_ref) / h_ref < 1e-4
    h_bf16 = PLUGIN.plugin_h(x, ref.Precision("bfloat16"))
    assert abs(h_bf16 - h_ref) / h_ref > 1e-4


def test_kde_answers_match_engine():
    cfg, _gen, data = _small("tpch-lineitem-sf1", rows=20000, capacity=512)
    store = harness.build_store(cfg, data)
    engine = store.engine(backend="jnp", selector="plugin")
    traffic = traffic_mod.load("tpch-mix")
    stats = {c: (float(v.min()), float(v.max())) for c, v in data.items()}
    rng = np.random.default_rng(3)
    joints = harness.joint_keys(cfg["store"])
    specs = [traffic_mod.make_spec(t, rng, stats)
             for t in traffic["block"][:5] for _ in range(3)]
    results = engine.execute([traffic_mod.to_query(s) for s in specs])
    expanded = []
    for s in specs:
        expanded += [s] * (3 if s["group_by"] else 1)
    assert len(expanded) == len(results)
    for s, r in zip(expanded, results):
        key = harness.spec_key(s, joints)
        cols = key if isinstance(key, tuple) else (key,)
        prog = store.joints[key] if isinstance(key, tuple) \
            else store.columns[key]
        x = prog.sample()
        (est, half, count, m_t), = PLUGIN.answers(
            [ref.box_of(s, cols, r.group)], [s["agg"]], x,
            PLUGIN.bandwidth(x, ref.F64), prog.n_seen, ref.F64)
        from bench.compare import gap
        assert gap(s["agg"], r.estimate - est, prog.n_seen, m_t, count) < 1e-5
        assert gap(s["agg"], (r.ci_hi - r.ci_lo) / 2 - half, prog.n_seen,
                   m_t, count) < 1e-5
